"""Sharding rules: param-path regex -> PartitionSpec (DP/TP/EP + batch DP).

Mesh axes: single-pod ("data", "model") = (16, 16); multi-pod
("pod", "data", "model") = (2, 16, 16). Parameters are TP-sharded over
"model"; the batch is DP-sharded over ("pod", "data"). The pod axis carries
no parameter shards — cross-pod traffic is gradient reduction only
(hierarchical, DCN-friendly).

Column-parallel (out-dim "model"): wq/wk/wv, ffn w1/w3, up-projections,
expert w1/w3, vocab-sharded embedding. Row-parallel (in-dim "model"):
wo, ffn w2, down/out projections, expert w2 — GSPMD inserts the psum.
Quantization scales follow their weight's out-channel sharding. Everything
small (norms, gates, conv, biases of row-parallel layers) is replicated.
"""
from __future__ import annotations

import re
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (regex on 'a/b/c' joined path, spec for the LAST ndims; left-padded w/ None)
_RULES: list[tuple[str, tuple]] = [
    (r"(^|/)embed$", ("model", None)),
    (r"(^|/)pos_embed$", (None, None)),
    (r"(^|/)lm_head$", (None, "model")),
    (r"(^|/)router$", (None, None)),
    (r"(^|/)(wq|wk|wv|w1|w3|wqkv|w13|up|in_z|in_x|w_in)/(w|wq)$", (None, "model")),
    (r"(^|/)(wq|wk|wv|w1|w3|wqkv|w13|up|in_z|in_x|w_in)/s_w$", (None, "model")),
    (r"(^|/)(wq|wk|wv|w1|w3|wqkv|w13|up|in_z|in_x|w_in)/b$", ("model",)),
    (r"(^|/)(wo|w2|down|out_proj)/(w|wq)$", ("model", None)),
    (r"(^|/)w_gates/w$", (None, None)),
]


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def spec_for(path, leaf) -> P:
    ndim = getattr(leaf, "ndim", len(getattr(leaf, "shape", ())))
    s = _path_str(path)
    for pat, tail in _RULES:
        if re.search(pat, s):
            tail = tail[-ndim:] if ndim < len(tail) else tail
            pad = (None,) * (ndim - len(tail))
            return P(*(pad + tuple(tail)))
    return P(*((None,) * ndim))


def param_specs(params, fsdp_axes: tuple = (), fsdp_min_dim: int = 2) -> dict:
    """Pytree of PartitionSpec matching ``params`` structure.

    ``fsdp_axes``: ZeRO-style weight/optimizer sharding — stacked-layer
    leaves additionally shard their LEADING (layer) dim over these axes when
    divisible. The per-layer dynamic-slice inside the scan then all-gathers
    one layer's shard at use (FSDP semantics); gradients arrive reduce-
    scattered. Cuts params+Adam memory by the data-axis size.
    """
    def spec(p, l):
        s = spec_for(p, l)
        if fsdp_axes and l.ndim > fsdp_min_dim and s[0] is None:
            # leading dim is a layer/group stack dim for every >2D leaf;
            # fall back to an axis subset when the stack doesn't divide the
            # full DP product (e.g. 80 layers on pod*data = 32 -> data = 16)
            for k in range(len(fsdp_axes)):
                axes = fsdp_axes[k:]
                if l.shape[0] % _axes_size(axes) == 0:
                    return P(axes if len(axes) > 1 else axes[0], *s[1:])
        return s
    return jax.tree_util.tree_map_with_path(spec, params)


_AXSZ: dict = {}


def set_mesh_axis_sizes(mesh: Mesh):
    global _AXSZ
    _AXSZ = {a: mesh.shape[a] for a in mesh.axis_names}


def _axes_size(axes: tuple) -> int:
    n = 1
    for a in axes:
        n *= _AXSZ.get(a, 1)
    return n


def batch_spec(mesh: Mesh, ndim: int, batch_axis: int = 0) -> P:
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    spec = [None] * ndim
    spec[batch_axis] = dp if len(dp) > 1 else dp[0]
    return P(*spec)


def safe_batch_spec(mesh: Mesh, shape: tuple, batch_axis: int = 0) -> P:
    """batch_spec, dropping DP sharding when the batch doesn't divide
    (long_500k has global_batch=1)."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if shape[batch_axis] % n_dp != 0:
        return P(*((None,) * len(shape)))
    return batch_spec(mesh, len(shape), batch_axis)


def state_specs(state_tree, mesh: Mesh) -> dict:
    """NamedShardings for decode state, shape/divisibility-aware.

    KV caches (..., B, S, H, dh): batch over DP when divisible; the model
    axis goes on HEADS when the head count divides it, else on the SEQUENCE
    dim (context-parallel decode: each model shard holds a cache stripe,
    scores computed locally, GSPMD reduces the tiny softmax/output terms).
    SSM/mLSTM states: batch over DP; inner (channel/value) dim over model
    when divisible (consistent with column-parallel value projections).
    """
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dpa = dp if len(dp) > 1 else dp[0]
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    n_model = mesh.shape.get("model", 1)

    def spec(path, leaf):
        ndim = leaf.ndim
        shape = leaf.shape
        s = _path_str(path)
        if ndim == 0 or "len" in s:
            return P(*((None,) * ndim))
        sp = [None] * ndim
        if s.endswith("/k") or s.endswith("/v") or s in ("k", "v"):
            b_dim, s_dim, h_dim = ndim - 4, ndim - 3, ndim - 2
            if shape[b_dim] % n_dp == 0:
                sp[b_dim] = dpa
            if shape[h_dim] % n_model == 0:
                sp[h_dim] = "model"
            elif shape[s_dim] % n_model == 0:
                sp[s_dim] = "model"
            return P(*sp)
        if "conv" in s:          # (..., B, K, C): channels over model
            if shape[ndim - 3] % n_dp == 0:
                sp[ndim - 3] = dpa
            if shape[ndim - 1] % n_model == 0:
                sp[ndim - 1] = "model"
            return P(*sp)
        if s.endswith("ssm") or "/C" in s or s.endswith("C"):
            # (..., B, H, P, N) or mlstm C (..., B, H, dk, dv)
            if ndim >= 4 and shape[ndim - 4] % n_dp == 0:
                sp[ndim - 4] = dpa
            if s.endswith("C") and shape[ndim - 1] % n_model == 0:
                sp[ndim - 1] = "model"   # value dim (wv col-parallel)
            elif shape[ndim - 3] % n_model == 0:
                sp[ndim - 3] = "model"   # heads
            return P(*sp)
        # generic small states (n/m/h/c): batch over DP only
        for d in range(ndim):
            size_ok = shape[d] % n_dp == 0 and shape[d] >= n_dp
            if size_ok and d >= ndim - 3 and shape[d] > 1:
                sp[d] = dpa
                break
        return P(*sp)
    return jax.tree_util.tree_map_with_path(spec, state_tree)


# ---------------------------------------------------- serving (tp) specs
# DESIGN.md §16: tensor-parallel serving reuses the training _RULES for the
# packed weight tree (column-parallel wq/wk/wv/wqkv/w1/w3/w13, row-parallel
# wo/w2 — GSPMD inserts the int32 psum), with two serving-only overrides and
# a KV-head rule the training state_specs never needed.

#: replicated in serving regardless of the training rule: logits feed the
#: fp sampler, whose reduction order must match tp=1 EXACTLY for the
#: byte-identical-streams bar — so the lm_head matmul (and the embedding
#: gather feeding it through tied weights) runs replicated. Both are a
#: small fraction of the int4 footprint; vocab sharding is a training
#: memory concern, not a serving one.
_SERVING_REPLICATED = re.compile(r"(^|/)(embed|pos_embed|lm_head)$")


def serving_param_specs(params) -> dict:
    """PartitionSpec tree for a DEPLOYED (packed-int) param tree under the
    serving ("model",) mesh.

    Same regex table as training ``param_specs`` — packed codes keep their
    weight's spec: column-parallel shards the out dim N (nibbles pack along
    K, so N-sharding never splits a pair); row-parallel shards the PACKED
    K/2 rows (divisibility enforced at plan build). Scales ``s_w`` (1, N)
    follow their weight's out-channel sharding; activation scales ``s_a``
    and row-parallel biases fall through to replicated.
    """
    def spec(path, leaf):
        if _SERVING_REPLICATED.search(_path_str(path)):
            ndim = getattr(leaf, "ndim", len(getattr(leaf, "shape", ())))
            return P(*((None,) * ndim))
        return spec_for(path, leaf)
    return jax.tree_util.tree_map_with_path(spec, params)


def serving_state_specs(state_tree, mesh: Mesh) -> dict:
    """KV-head partitioning for the serving decode state and the paged
    block-pool buffers (DESIGN.md §16).

    The training ``state_specs`` rule only knows the fp ``k``/``v`` rows;
    serving also carries the quantized layout (DESIGN.md §8):

    ===============  ==============================  =====================
    leaf             shape                            "model" axis
    ===============  ==============================  =====================
    k / v            (L, B, S, H_kv, hd)              heads (ndim-2)
    k_q / v_q        (L, B, S, H_kv, ceil(hd/2))      heads (ndim-2)
    k_scale/v_scale  (L, B, S, H_kv)                  heads (ndim-1)
    len / cursors    host-side or per-slot ints       replicated
    ===============  ==============================  =====================

    KV codes pack along head_dim, so head sharding never splits a nibble
    pair. The same basenames cover the block pool's (L, NB, block, H_kv, .)
    buffers. Anything unmatched (or non-divisible) stays replicated —
    correct, just not partitioned.
    """
    n_model = mesh.shape.get("model", 1)

    def spec(path, leaf):
        ndim, shape = leaf.ndim, leaf.shape
        base = _path_str(path).rsplit("/", 1)[-1]
        sp = [None] * ndim
        if base in ("k", "v", "k_q", "v_q") and ndim >= 2 \
                and shape[ndim - 2] % n_model == 0:
            sp[ndim - 2] = "model"
        elif base in ("k_scale", "v_scale") and ndim >= 1 \
                and shape[ndim - 1] % n_model == 0:
            sp[ndim - 1] = "model"
        return P(*sp)
    return jax.tree_util.tree_map_with_path(spec, state_tree)


def place_serving(tree, mesh: Mesh, specs):
    """``device_put`` under NamedShardings — both the initial host→mesh
    placement in ``deploy()`` and the reshard-on-load path (artifacts store
    full logical arrays, so resharding to a different tp is pure
    placement)."""
    return jax.device_put(tree, shardings_for(tree, mesh, specs))


def shardings_for(tree, mesh: Mesh, specs=None):
    specs = specs if specs is not None else param_specs(tree)
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                        is_leaf=lambda x: isinstance(x, P))


def make_mesh(shape: tuple, axes: tuple) -> Mesh:
    """A device mesh with ``Auto`` axes: every sharding rule here is a
    GSPMD hint (``with_sharding_constraint``), which ``jax.make_mesh``'s
    default ``Explicit`` axes (JAX >= 0.7) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
