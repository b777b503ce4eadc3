"""Pallas TPU kernel: fused decode attention over a quantized KV cache.

One decode step attends a single new token per slot against that slot's
cached K/V (DESIGN.md §8). With the cache quantized (int8, or int4 nibbles
packed along head_dim), the dominant HBM stream of a decode step — reading
S_max * Hkv * hd K/V floats per layer — drops 4-8x: the kernel DMAs the
*packed* codes plus one f32 scale per (token, head) row and dequantizes
blocks in VMEM inside the online-softmax loop. The fp32 (B, S) score matrix
never exists in HBM either.

Layout: grid (B,); each program owns one slot and ALL its Hkv kv-heads, so
every K/V/scale block spans the full (Hkv, dhp) / (S, Hkv) trailing dims — the
only blocking of the (B, S, Hkv, dhp) cache layout Mosaic accepts (a 1-wide
head block in the second-minor dim is refused). Heads are a static loop
inside the program; each serves its ``group`` query heads (GQA). The loop
walks the cache in ``bs``-row blocks carrying (acc, m, l); rows at positions
>= the slot's cursor are masked (per-slot lengths — serving refills slots
independently).
The current token's K/V arrive unquantized and are folded in after the loop:
the new token attends itself at full precision, and the cache write
(quantize-on-append, models/transformer.write_new_kv) decides what future
steps see.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kv_pack import nibbles_i32

NEG_INF = -2.0e38
DEFAULT_BS = 128


def _interleave_lanes(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """(bs, d/2) x2 int32 codes -> (bs, d) f32 with lo at even lanes.

    The same values as ``kv_pack.unpack_nibbles_last``, but the lane
    interleave is two matmuls against 0/1 selection matrices: every output
    is one code times 1 plus zeros, exact at any matmul precision, and
    Mosaic compiles it in seconds where a stack+reshape lane interleave
    unrolled over 32 heads takes minutes."""
    half = lo.shape[-1]
    r = jax.lax.broadcasted_iota(jnp.int32, (half, 2 * half), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (half, 2 * half), 1)
    even = (c == 2 * r).astype(jnp.float32)
    odd = (c == 2 * r + 1).astype(jnp.float32)
    return (jnp.dot(lo.astype(jnp.float32), even)
            + jnp.dot(hi.astype(jnp.float32), odd))


def _dequant_rows(codes: jax.Array, scales: jax.Array) -> jax.Array:
    """(bs, dhp) codes + (bs,) scales -> (bs, dh) f32 rows in VMEM."""
    if codes.dtype == jnp.uint8:
        codes = _interleave_lanes(*nibbles_i32(codes))
    return codes.astype(jnp.float32) * scales[:, None]


def _kernel(q_ref, kq_ref, vq_ref, ks_ref, vs_ref, kn_ref, vn_ref, len_ref,
            o_ref, *, bs: int, scale: float):
    S, Hkv = kq_ref.shape[1], kq_ref.shape[2]
    n_blk = S // bs
    ln = len_ref[pl.program_id(0)]
    for h in range(Hkv):
        o_ref[0, h] = _attend_head(q_ref, kq_ref, vq_ref, ks_ref, vs_ref,
                                   kn_ref, vn_ref, h, ln, n_blk, bs=bs,
                                   scale=scale).astype(o_ref.dtype)


def _attend_head(q_ref, kq_ref, vq_ref, ks_ref, vs_ref, kn_ref, vn_ref,
                 h: int, ln, n_blk: int, *, bs: int, scale: float):
    """Online-softmax attention of kv-head ``h``'s query group -> (G, dh)."""
    q = q_ref[0, h].astype(jnp.float32) * scale          # (G, dh)
    G, dh = q.shape

    def body(j, carry):
        acc, m, l = carry
        k = _dequant_rows(kq_ref[0, pl.ds(j * bs, bs), h, :],
                          ks_ref[0, pl.ds(j * bs, bs), h])       # (bs, dh)
        v = _dequant_rows(vq_ref[0, pl.ds(j * bs, bs), h, :],
                          vs_ref[0, pl.ds(j * bs, bs), h])
        s = q @ k.T                                              # (G, bs)
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (G, bs), 1)
        s = jnp.where(pos < ln, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + p @ v
        return acc_new, m_new, l_new

    acc = jnp.zeros((G, dh), jnp.float32)
    m = jnp.full((G,), NEG_INF, jnp.float32)
    l = jnp.zeros((G,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_blk, body, (acc, m, l))

    # fold in the current token (fp K/V; it always attends itself)
    kn = kn_ref[0, h].astype(jnp.float32)                # (dh,)
    vn = vn_ref[0, h].astype(jnp.float32)
    s_n = q @ kn                                         # (G,)
    m_new = jnp.maximum(m, s_n)
    p_n = jnp.exp(s_n - m_new)
    corr = jnp.exp(m - m_new)
    l = l * corr + p_n
    acc = acc * corr[:, None] + p_n[:, None] * vn[None, :]
    return acc / jnp.maximum(l, 1e-30)[:, None]


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def decode_attention_pallas(q: jax.Array, k_q: jax.Array, v_q: jax.Array,
                            k_scale: jax.Array, v_scale: jax.Array,
                            k_new: jax.Array, v_new: jax.Array,
                            lengths: jax.Array, *, bs: int = DEFAULT_BS,
                            interpret: bool = False) -> jax.Array:
    """q: (B, H, dh) float; k_q/v_q: (B, S, Hkv, dhp) int8 (dhp=dh) or uint8
    packed nibbles (dhp=dh/2); k_scale/v_scale: (B, S, Hkv) f32 per-row
    scales; k_new/v_new: (B, Hkv, dh) float; lengths: (B,) int32 per-slot
    cursors. Returns (B, H, dh) in q.dtype."""
    B, H, dh = q.shape
    S, Hkv = k_q.shape[1], k_q.shape[2]
    group = H // Hkv
    assert H % Hkv == 0, (H, Hkv)
    assert S % bs == 0, (S, bs)
    scale = 1.0 / float(dh) ** 0.5
    qg = q.reshape(B, Hkv, group, dh)
    lens = lengths.astype(jnp.int32).reshape(B)

    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, scale=scale),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hkv, group, dh), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((1, S, Hkv, k_q.shape[-1]), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((1, S, Hkv, v_q.shape[-1]), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((1, S, Hkv), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, S, Hkv), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Hkv, dh), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, Hkv, dh), lambda b: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),     # all B cursors
        ],
        out_specs=pl.BlockSpec((1, Hkv, group, dh), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, dh), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(qg, k_q, v_q, k_scale, v_scale, k_new, v_new, lens)
    return out.reshape(B, H, dh)


# ------------------------------------------------------- paged indirection
def gather_kv_blocks(buf: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Block-pool buffer (NB, block, ...) + per-slot tables (B, nb) ->
    dense-layout view (B, nb*block, ...).

    ``mode='clip'`` clamps out-of-range table entries (the pool pads
    tables with its ``num_blocks`` sentinel) — jnp.take's default fill
    mode would inject NaN, which survives even fully-masked positions as
    ``0 * NaN``. Clamped positions surface arbitrary resident rows — safe
    by the same argument that makes the dense layout's stale rows safe:
    every position >= the slot's length is replaced with ``NEG_INF``
    before the softmax (``_kernel`` above and the jnp reference path
    alike), so garbage rows contribute *exact zeros* to the output,
    keeping paged bit-identical to dense."""
    g = jnp.take(buf, block_tables, axis=0,
                 mode="clip")                      # (B, nb, block, ...)
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *g.shape[3:])


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def decode_attention_paged(q: jax.Array, k_q_blocks: jax.Array,
                           v_q_blocks: jax.Array, k_scale_blocks: jax.Array,
                           v_scale_blocks: jax.Array,
                           block_tables: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, lengths: jax.Array, *,
                           bs: int = DEFAULT_BS,
                           interpret: bool = False) -> jax.Array:
    """Paged-layout entry point: one per-layer gather of block indices,
    then the UNCHANGED in-VMEM dequant online-softmax loop.

    ``*_blocks`` are block-pool buffers for ONE layer, (NB, block, Hkv, ...)
    — the pool's layer-major (L, NB, ...) arrays indexed at a layer.
    ``block_tables`` is (B, nb) int32 with nb*block == the dense S (a
    multiple of ``bs`` after the engine's bucket rounding). Output is
    bit-identical to ``decode_attention_pallas`` on the dense layout the
    tables describe. The jnp reference path gets the same indirection one
    level up: the engine gathers a dense-shaped cache view per step (see
    ``serving/block_pool.py``) and feeds the existing reference attention.
    """
    k_q = gather_kv_blocks(k_q_blocks, block_tables)
    v_q = gather_kv_blocks(v_q_blocks, block_tables)
    k_scale = gather_kv_blocks(k_scale_blocks, block_tables)
    v_scale = gather_kv_blocks(v_scale_blocks, block_tables)
    return decode_attention_pallas(q, k_q, v_q, k_scale, v_scale,
                                   k_new, v_new, lengths, bs=bs,
                                   interpret=interpret)
