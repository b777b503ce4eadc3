"""KV-cache quantization + nibble helpers shared with the matmul kernels.

The serving KV cache (DESIGN.md §8) stores K/V as integer codes with
per-head, per-token scales:

    codes[..., h, :] = round(x[..., h, :] / s[..., h])    s = amax_hd(|x|) / qmax

* ``kv_bits=8``: int8 codes on the symmetric [-127, 127] grid.
* ``kv_bits=4``: the paper's k=4 grid clamped symmetric to [-7, 7] and packed
  two codes per byte along head_dim (bias +7 into unsigned nibbles, same
  byte layout as the int4 weight packing in ``core/packing`` /
  ``kernels/int4_matmul`` — only the packing axis differs: head_dim here,
  the contracting K axis there).

Per-token granularity means appending one decode step's K/V never touches
another row's scale — quantize-on-append composes with the per-slot scatter
writes that keep serving slots isolated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INT4_BIAS = 7  # maps [-7, 8] -> [0, 15]; mirrors core.packing.INT4_BIAS


def kv_qmax(bits: int) -> int:
    """Symmetric clamp bound: 127 for int8, 7 for int4 (|qmin| of the paper's
    asymmetric [-7, 8] grid, so negative outliers are never clipped harder
    than positive ones)."""
    if bits == 8:
        return 127
    if bits == 4:
        return 7
    raise ValueError(f"kv_bits must be 4 or 8, got {bits}")


def nibbles_i32(packed: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Low and high nibbles of packed uint8 bytes as int32 codes in [-7, 8].

    The arithmetic runs in int32 because Mosaic has no int8 vector subtract;
    callers interleave the two and cast once, at the end."""
    w = packed.astype(jnp.int32)
    return (w & 0xF) - INT4_BIAS, (w >> 4) - INT4_BIAS


def unpack_nibbles_rows(wp: jax.Array) -> jax.Array:
    """(K/2, N) uint8 -> (K, N) int8 in [-7, 8]; row 2i from the low nibble.

    The int4 weight-matmul kernels unpack their HBM slabs with this (packing
    along the contracting axis = rows of the weight block).
    """
    lo, hi = nibbles_i32(wp)
    kk, n = wp.shape
    return jnp.stack([lo, hi], axis=1).reshape(kk * 2, n).astype(jnp.int8)


def pack_nibbles_last(codes: jax.Array) -> jax.Array:
    """(..., d) int codes in [-7, 8] -> (..., d/2) uint8; element 2i in the
    low nibble. ``d`` must be even (head_dim always is with RoPE)."""
    d = codes.shape[-1]
    assert d % 2 == 0, f"pack axis extent must be even, got {d}"
    biased = (codes.astype(jnp.int32) + INT4_BIAS).astype(jnp.uint8)
    lo = biased[..., 0::2]
    hi = biased[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_nibbles_last(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_nibbles_last`: (..., d/2) uint8 -> (..., d) int8."""
    lo, hi = nibbles_i32(packed)
    stacked = jnp.stack([lo, hi], axis=-1)          # (..., d/2, 2)
    return stacked.reshape(*packed.shape[:-1],
                           packed.shape[-1] * 2).astype(jnp.int8)


def quantize_kv(x: jax.Array, bits: int) -> tuple[jax.Array, jax.Array]:
    """Quantize K or V rows with per-head, per-token scales.

    x: (..., H, hd) float -> (codes, scales) with
      codes:  (..., H, hd) int8          for bits=8
              (..., H, hd/2) uint8       for bits=4 (packed nibbles)
      scales: (..., H) f32, amax over head_dim / qmax (eps-floored so all-zero
              rows — cache padding — quantize to exact zeros).
    """
    qmax = kv_qmax(bits)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scales = jnp.maximum(amax / qmax, 1e-8)
    codes = jnp.clip(jnp.round(xf / scales[..., None]), -qmax, qmax
                     ).astype(jnp.int8)
    if bits == 4:
        return pack_nibbles_last(codes), scales
    return codes, scales


def dequantize_kv(codes: jax.Array, scales: jax.Array,
                  dtype=jnp.float32) -> jax.Array:
    """(codes, scales) -> (..., H, hd) float. The code dtype carries the bit
    width: uint8 rows are packed int4 nibbles, int8 rows are bare codes."""
    if codes.dtype == jnp.uint8:
        codes = unpack_nibbles_last(codes)
    return (codes.astype(jnp.float32) * scales[..., None]).astype(dtype)


def kv_code_shape(hd: int, bits: int) -> int:
    """Trailing (head_dim) extent of the code buffer for one K/V row."""
    if bits == 4:
        assert hd % 2 == 0, f"int4 KV packing needs even head_dim, got {hd}"
        return hd // 2
    return hd


def kv_code_dtype(bits: int):
    return jnp.uint8 if bits == 4 else jnp.int8


def kv_buffer_keys(bits: int) -> tuple[str, ...]:
    """The K/V buffer names of a cache state at this precision — the keys a
    row-copy (slot scatter, prefix-cache entry) must carry alongside 'len'.
    Shared by serving/kv_cache and serving/prefix_cache so the packed layout
    is spelled out exactly once."""
    if bits in (8, 4):
        return ("k_q", "v_q", "k_scale", "v_scale")
    if bits == 16:
        return ("k", "v")
    raise ValueError(f"kv_bits must be 16, 8 or 4, got {bits}")


def kv_row_bytes(n_kv: int, hd: int, bits: int, *,
                 fp_bytes: int = 4) -> int:
    """Bytes one cached token row costs across K+V per layer: codes + per-
    (token, head) f32 scales for bits 8/4, plain fp rows for 16. This is the
    quantity the prefix cache's byte budget buys — int4 rows are ~7x smaller
    than f32, so the same budget holds ~7x more reusable prefix tokens."""
    if bits == 16:
        return 2 * n_kv * hd * fp_bytes
    return 2 * (n_kv * kv_code_shape(hd, bits) + n_kv * 4)
