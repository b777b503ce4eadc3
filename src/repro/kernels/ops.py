"""jit'd public wrappers for the Pallas kernels (tiling, padding, dtypes).

On TPU the kernels compile to Mosaic. On the CPU backend they run in
interpret mode (kernel body executed by XLA:CPU) so the SAME code path is
testable offline; any other backend is an error rather than a silent
interpreter. ``qlinear`` dispatches here when ``QuantSpec.use_pallas`` is set.

Tiles: Mosaic accepts a block whose last two dims are multiples of
(8, 128) or equal to the array's dims. :func:`_tile` picks such blocks; a
dim with no legal divisor is padded with zero codes, which add exactly 0 to
the int32 accumulator, and the result is sliced back — bit-identical to the
unpadded product.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .act_quant import act_quant_pallas
from .decode_attention import decode_attention_pallas
from .int4_matmul import int4_matmul_fused_pallas, int4_matmul_pallas
from .int8_matmul import int8_matmul_pallas
from .kv_pack import INT4_BIAS

ROWS, LANES = 8, 128          # Mosaic's (sublane, lane) block alignment
ACT_BLOCK_BYTES = 2 << 20     # f32 input block of the act_quant kernel
_ZERO_NIBBLES = INT4_BIAS | (INT4_BIAS << 4)   # packed byte of two 0 codes


def _interpret() -> bool:
    """False on TPU (Mosaic), True on CPU (interpret mode, for tests)."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for TPU and are interpreted on CPU "
        f"only; backend {backend!r} is neither (serve with "
        f"backend='reference' there)")


def _tile(dim: int, target: int, align: int) -> tuple[int, int]:
    """(block, padded_dim) for one blocked axis.

    ``dim <= target`` takes the whole axis. Otherwise the block is the
    largest multiple of ``align`` <= ``target`` dividing ``dim``; if none
    exists the axis is padded up to a multiple of ``align``."""
    if dim <= target:
        return dim, dim
    padded = -(-dim // align) * align
    for p in (dim, padded):
        for b in range(target // align * align, 0, -align):
            if p % b == 0:
                return b, p
    raise AssertionError("unreachable: align divides padded")


def _pad_axis(x, size: int, axis: int, value=0):
    if x.shape[axis] == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, pad, constant_values=value)


def act_quant(x: jax.Array, s: jax.Array, bits: int = 8) -> jax.Array:
    lead = x.shape[:-1]
    M, K = math.prod(lead), x.shape[-1]
    # rows per block: an f32 block of at most ACT_BLOCK_BYTES, because the
    # kernel's VMEM stack is about five blocks and the limit is 16 MiB
    # (256 rows of bert-base's K=3072 already exceed it)
    rows = max(ROWS, min(256, ACT_BLOCK_BYTES // (4 * K)))
    bm, Mp = _tile(M, rows, ROWS)
    x2 = _pad_axis(x.reshape(M, K), Mp, 0)
    out = act_quant_pallas(x2, s, bits=bits, bm=bm, interpret=_interpret())
    return out[:M].reshape(*lead, K)


def _matmul_tiles(M: int, K: int, N: int):
    """(bm, Mp), (bn, Np), (bk, Kp) for the int8/int4 matmul grids. The K
    block is a multiple of 128 (or all of K), so an int4 slab's bk/2 packed
    rows are a legal uint8 block too."""
    return (_tile(M, 128, ROWS), _tile(N, 128, LANES), _tile(K, 512, LANES))


def int8_matmul(x: jax.Array, w8: jax.Array, s_a: jax.Array, s_w: jax.Array,
                a_bits: int = 8) -> jax.Array:
    """x: (M, K) float -> quantize -> int8 GEMM -> dequant. w8: (K, N) int8."""
    x8 = act_quant(x, s_a, bits=a_bits)
    M, K = x8.shape
    N = w8.shape[1]
    (bm, Mp), (bn, Np), (bk, Kp) = _matmul_tiles(M, K, N)
    x8 = _pad_axis(_pad_axis(x8, Mp, 0), Kp, 1)
    w8 = _pad_axis(_pad_axis(w8, Kp, 0), Np, 1)
    s_w = _pad_axis(s_w.reshape(1, N), Np, 1)
    out = int8_matmul_pallas(x8, w8, s_a, s_w, bm=bm, bn=bn, bk=bk,
                             out_dtype=x.dtype, interpret=_interpret())
    return out[:M, :N]


def int4_matmul(x: jax.Array, wp: jax.Array, s_a: jax.Array, s_w: jax.Array,
                a_bits: int = 8, bias: jax.Array | None = None,
                act: str | None = None) -> jax.Array:
    """x: (M, K) float; wp: (K/2, N) packed nibbles.

    ``act`` selects the fused decode path: dequant + bias + activation run in
    the kernel epilogue (one HBM write of the (M, N) result instead of three).
    With ``act`` set, ``bias`` (or zeros) is folded in as well.
    """
    x8 = act_quant(x, s_a, bits=a_bits)
    M = x8.shape[0]
    K, N = wp.shape[0] * 2, wp.shape[1]   # packing padded K to even
    (bm, Mp), (bn, Np), (bk, Kp) = _matmul_tiles(M, K, N)
    x8 = _pad_axis(_pad_axis(x8, Mp, 0), Kp, 1)
    wp = _pad_axis(_pad_axis(wp, Kp // 2, 0, _ZERO_NIBBLES), Np, 1,
                   _ZERO_NIBBLES)
    s_w = _pad_axis(s_w.reshape(1, N), Np, 1)
    if act is not None:
        b = (jnp.zeros((1, N), jnp.float32) if bias is None
             else bias.reshape(1, N).astype(jnp.float32))
        out = int4_matmul_fused_pallas(
            x8, wp, s_a, s_w, _pad_axis(b, Np, 1), act=act, bm=bm, bn=bn,
            bk=bk, out_dtype=x.dtype, interpret=_interpret())
    else:
        out = int4_matmul_pallas(x8, wp, s_a, s_w, bm=bm, bn=bn, bk=bk,
                                 out_dtype=x.dtype, interpret=_interpret())
    return out[:M, :N]


def decode_attention(q: jax.Array, k_q: jax.Array, v_q: jax.Array,
                     k_scale: jax.Array, v_scale: jax.Array,
                     k_new: jax.Array, v_new: jax.Array,
                     lengths: jax.Array) -> jax.Array:
    """Decode attention over a quantized KV cache (DESIGN.md §8).

    q: (B, H, dh) float — ONE new token per slot; k_q/v_q: (B, S, Hkv, dhp)
    int8 codes or packed int4 nibbles; k_scale/v_scale: (B, S, Hkv) per-row
    scales; k_new/v_new: (B, Hkv, dh) the current token's fp K/V; lengths:
    per-slot cursors — scalar or (B,). Returns (B, H, dh).
    """
    B, S = q.shape[0], k_q.shape[1]
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1), (B,))
    # row step of the in-VMEM loop: a sublane-aligned divisor of S, or all
    # of S at once (the cache is never padded: that would copy it)
    bs, s_pad = _tile(S, 128, ROWS)
    return decode_attention_pallas(q, k_q, v_q, k_scale, v_scale,
                                   k_new, v_new, lens,
                                   bs=S if s_pad != S else bs,
                                   interpret=_interpret())
