"""QAT pipeline: calibration -> fake-quant training -> int deployment.

Calibration (paper §3.1):
* weight scales: abs-max per output channel / l_max(bits-of-that-layer) —
  a pure tree transform (handles stacked layer/group/expert leading dims).
* activation scales: run N forward batches in ``calibration_mode`` (models
  swap lax.scan for an eager layer loop); every quantizable matmul reports
  percentile(|input|) in deterministic call order; the stream is folded back
  onto the ``s_a`` leaves by per-family site order.

Deployment: ``deploy_params`` splits stacked layers at segment boundaries and
replaces every fp weight with packed int4 / int8 codes (core.packing) so the
int inference path (and its Pallas kernels) can run.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from . import calibration
from .packing import quantize_weight
from .policy import QuantPolicy
from .quantizer import qrange

# ---------------------------------------------------------------- weight scales

_LINEAR_KEYS = ("w",)


def _is_linear(node) -> bool:
    return isinstance(node, dict) and "w" in node and "s_w" in node


@jax.jit
def _absmax_k(w):
    """Per-out-channel |w| max over the K axis (second-to-last), fused so
    no full-size |w| temporary is made."""
    return jnp.max(jnp.abs(w), axis=-2, keepdims=True)


def calibrate_weight_scales(params, bits_for_leaf: Callable[[tuple], np.ndarray]):
    """Set every linear's s_w = absmax_per_outchannel / l_max(bits).

    ``bits_for_leaf(shape_prefix)`` returns per-layer/group bits broadcastable
    to the leaf's leading (stacked) dims; scalar for unstacked.
    """
    def walk(node, prefix):
        if _is_linear(node):
            w = node["w"]
            s_w = node["s_w"]
            absmax = _absmax_k(w)
            bits = np.asarray(bits_for_leaf(w.shape[:-2]), np.float32)
            # qrange-consistent l_max: 2^{k-1} for k<8, 127 for the int8 carrier
            qmax = jnp.asarray(np.where(bits >= 8, 2.0 ** (bits - 1) - 1,
                                        2.0 ** (bits - 1)))
            qmax = qmax.reshape(qmax.shape + (1,) * (absmax.ndim - qmax.ndim))
            new = dict(node)
            new["s_w"] = jnp.maximum(absmax / qmax, 1e-8).astype(s_w.dtype)
            return new
        if isinstance(node, dict):
            return {k: walk(v, prefix + (k,)) for k, v in node.items()}
        return node
    return walk(params, ())


def default_bits_fn(cfg: ModelConfig, policy: QuantPolicy):
    """Per-leaf bits resolver honoring stacked layer/group leading dims."""
    n_units = policy.num_layers
    per = {"xlstm": cfg.slstm_every, "hybrid": cfg.attn_every}.get(cfg.family)
    bits_vec = np.array([policy.weight_bits(l) or 32 for l in range(n_units)],
                        np.float32)

    def fn(shape_prefix: tuple) -> np.ndarray:
        if len(shape_prefix) == 0:
            return np.float32(policy.default_bits)
        L = shape_prefix[0]
        if per is not None:  # group-stacked (G, ...) or (G, per, ...)
            G = n_units // per
            if L == G:
                gbits = np.array([policy.weight_bits(g * per) or 32
                                  for g in range(G)], np.float32)
                out = gbits
            else:
                out = np.full(L, policy.default_bits, np.float32)
        elif L == n_units:
            out = bits_vec
        else:  # expert dim or other stacked dim: default bits
            out = np.full(L, policy.default_bits, np.float32)
        extra = shape_prefix[1:]
        return out.reshape((L,) + (1,) * len(extra))
    return fn


# ---------------------------------------------------------------- act scales

SITE_ORDERS = {
    # per-layer quantized-matmul input records, in model code order
    "attn": ["attn/wq", "attn/wk", "attn/wv", "attn/wo"],
    "ffn_swiglu": ["ffn/w1", "ffn/w3", "ffn/w2"],
    "ffn_gelu": ["ffn/w1", "ffn/w2"],
    "moe": ["moe/w1", "moe/w3", "moe/w2"],
}


def site_order(cfg: ModelConfig) -> list[str]:
    if cfg.family == "moe":
        sites = SITE_ORDERS["attn"] + SITE_ORDERS["moe"]
        if cfg.shared_expert_d_ff:
            sites = sites + ["moe/shared/w1", "moe/shared/w3", "moe/shared/w2"]
        return sites
    ffn = SITE_ORDERS["ffn_swiglu"] if cfg.act == "swiglu" else SITE_ORDERS["ffn_gelu"]
    return SITE_ORDERS["attn"] + ffn


def calibrate_act_scales(params, cfg: ModelConfig, policy: QuantPolicy,
                         forward_fn: Callable, batches: list[dict],
                         percentile: float = 99.99):
    """Transformer-family precise per-site calibration (dense/moe/vlm/bert).

    Non-transformer families use :func:`calibrate_act_scales_global`.
    """
    if cfg.family in ("xlstm", "hybrid", "encdec"):
        return calibrate_act_scales_global(params, cfg, policy, forward_fn,
                                           batches, percentile)
    sites = site_order(cfg)
    K = len(sites)
    L = cfg.num_layers
    with calibration.calibration_mode(percentile) as cm:
        for b in batches:
            forward_fn(params, b)
    rec = cm.records
    if len(rec) % (L * K) != 0:
        raise RuntimeError(
            f"calibration records {len(rec)} not divisible by L*K={L * K}; "
            "site order out of sync with model code")
    nb = len(rec) // (L * K)
    # aggregate max over batches -> per (layer, site)
    agg: list[list] = [[None] * K for _ in range(L)]
    i = 0
    for _ in range(nb):
        for l in range(L):
            for k in range(K):
                v = rec[i]
                i += 1
                agg[l][k] = v if agg[l][k] is None else np.maximum(agg[l][k], v)
    new_params = jax.tree.map(lambda a: a, params)  # shallow rebuild
    layers = dict(new_params["layers"])
    for k, site in enumerate(sites):
        parts = site.split("/")
        # navigate copy-on-write
        def set_in(d, parts, vals):
            d = dict(d)
            if len(parts) == 1:
                lin = dict(d[parts[0]])
                s_a = lin["s_a"]
                per_layer = np.stack([np.asarray(agg[l][k], np.float32)
                                      for l in range(L)])
                qmax = np.array([float(qrange(policy.act_bits(l) or 32)[1])
                                 for l in range(L)], np.float32)
                qmax = qmax.reshape((L,) + (1,) * (per_layer.ndim - 1))
                val = np.maximum(per_layer / qmax, 1e-8)
                lin["s_a"] = jnp.asarray(val.reshape(s_a.shape), s_a.dtype)
                d[parts[0]] = lin
                return d
            d[parts[0]] = set_in(d[parts[0]], parts[1:], vals)
            return d
        layers = set_in(layers, parts, None)
    new_params["layers"] = layers
    return new_params


def calibrate_act_scales_global(params, cfg, policy, forward_fn, batches,
                                percentile=99.99):
    """Fallback: one global percentile drives every s_a (documented approx)."""
    with calibration.calibration_mode(percentile) as cm:
        for b in batches:
            forward_fn(params, b)
    stat = float(max(np.max(r) for r in cm.records)) if cm.records else 1.0
    _, qmax = qrange(policy.default_bits)

    def walk(node):
        if _is_linear(node):
            new = dict(node)
            new["s_a"] = jnp.full_like(node["s_a"], max(stat / qmax, 1e-8))
            return new
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node
    return walk(params)


# ---------------------------------------------------------------- deployment

@functools.partial(jax.jit, static_argnames=("bits", "lo", "hi"))
def _pack_layers(w, s_w, *, bits: int, lo, hi):
    """Codes of layers [lo:hi) of one weight stack, sliced and quantized in
    one fused program: the f32 slice and the quantizer's f32 temporaries
    are never materialized, so a model whose fp weights fill most of one
    chip still deploys there."""
    return quantize_weight(w[lo:hi], s_w[lo:hi], bits)[0]


def _quantize_stack(tree, w_bits: int, lo=None, hi=None):
    """Layers [lo:hi) of a stacked tree (all of an unstacked one, the
    default); with ``w_bits`` every linear's fp 'w' becomes its packed
    codes 'wq'."""
    take = (lambda a: a) if lo is None and hi is None else (lambda a: a[lo:hi])

    def walk(node):
        if w_bits and _is_linear(node):
            new = {k: take(v) for k, v in node.items() if k != "w"}
            new["wq"] = _pack_layers(node["w"], node["s_w"], bits=w_bits,
                                     lo=lo, hi=hi)
            return new
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return take(node)
    return walk(tree)


def deploy_params(params, cfg: ModelConfig, segments) -> dict:
    """QAT params -> deployed int params (per-segment layer stacks).

    Low-level packer: ``repro.deploy.deploy(params, plan)`` wraps this into
    the saveable DeployedModel artifact (DESIGN.md §9).

    Dense/MoE/BERT/VLM: params['layers'] becomes a LIST of per-segment stacks.
    xlstm/hybrid: group stacks quantized per segment similarly; shared block
    (hybrid) quantized at the last segment's bits.
    """
    bits = lambda spec: spec.w_bits if spec.enabled else 0
    stacks = lambda tree: [_quantize_stack(tree, bits(spec), s, e)
                           for (s, e, spec) in segments]
    out = dict(params)
    if cfg.family in ("xlstm", "hybrid"):
        key = "mlstm" if cfg.family == "xlstm" else "mamba"
        out[key] = stacks(params[key])
        if cfg.family == "xlstm":
            out["slstm"] = stacks(params["slstm"])
        else:
            out["shared"] = _quantize_stack(params["shared"],
                                            bits(segments[-1][2]))
        return out
    if cfg.family == "encdec":
        out["enc"] = _quantize_stack(params["enc"], bits(segments[0][2]))
        out["dec"] = stacks(params["dec"])
        return out
    out["layers"] = stacks(params["layers"])
    return out
