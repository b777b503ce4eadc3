"""Pallas TPU kernel: packed-int4 weight matmul — the paper's deployed layer.

TPU adaptation of MKQ-BERT's int4 CUDA GEMM (DESIGN.md §3): weights live in
HBM as packed nibbles (two int4 codes per byte along K, bias +7 so the paper's
[-7, 8] grid maps to [0, 15]). Each grid step:

  1. DMA a (bk/2, bn) uint8 weight slab HBM->VMEM      (half the int8 bytes!)
  2. VPU nibble unpack -> (bk, bn) int8 (shift/mask, interleave via reshape)
  3. MXU int8 x int8 -> int32 accumulate into VMEM scratch
  4. last K step: fused dequant epilogue  acc * (s_a * s_w[n])

The memory win is exactly what the paper's 15x/1.25x monetizes: decode-time
linear layers are weight-bandwidth-bound, and int4 halves the dominant HBM
stream vs int8 (8x vs f32). Compute stays on the MXU's native int8 path since
TPUs have no int4 ALU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .int8_matmul import INT_DOT
from .kv_pack import INT4_BIAS, unpack_nibbles_rows as _unpack_nibbles

__all__ = ["INT4_BIAS", "int4_matmul_pallas", "int4_matmul_fused_pallas"]

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _apply_epilogue(r: jax.Array, act: str) -> jax.Array:
    """f32 epilogue activation; mirrors models.layers.act_fn exactly."""
    if act == "gelu":
        return jax.nn.gelu(r, approximate=True)
    if act == "relu":
        return jnp.maximum(r, 0.0)
    raise ValueError(f"unsupported fused activation {act!r}")


def _kernel(x_ref, wp_ref, sa_ref, sw_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w8 = _unpack_nibbles(wp_ref[...])
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w8, (((1,), (0,)), ((), ())),
        precision=INT_DOT, preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        scale = sa_ref[0, 0] * sw_ref[...]
        out_ref[...] = (acc_ref[...].astype(jnp.float32) * scale
                        ).astype(out_ref.dtype)


def _fused_kernel(x_ref, wp_ref, sa_ref, sw_ref, b_ref, out_ref, acc_ref, *,
                  n_k: int, act: str):
    """int4 matmul with the full decode-layer epilogue fused: the int32
    accumulator is dequantized, biased and activated in VMEM on the last K
    step — the (bm, bn) float intermediate never round-trips HBM (the
    two-kernel path pays 2x(M, N) f32 of traffic for bias+act)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w8 = _unpack_nibbles(wp_ref[...])
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w8, (((1,), (0,)), ((), ())),
        precision=INT_DOT, preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        scale = sa_ref[0, 0] * sw_ref[...]
        r = acc_ref[...].astype(jnp.float32) * scale
        r = r + b_ref[...]
        if act != "none":
            r = _apply_epilogue(r, act)
        out_ref[...] = r.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def int4_matmul_pallas(x8: jax.Array, wp: jax.Array, s_a: jax.Array,
                       s_w: jax.Array, *, bm=DEFAULT_BM, bn=DEFAULT_BN,
                       bk=DEFAULT_BK, out_dtype=jnp.float32,
                       interpret: bool = False) -> jax.Array:
    """x8: (M, K) int8 (int4-grid codes), wp: (K/2, N) uint8 packed nibbles,
    s_a: () f32 activation scale, s_w: (1, N) f32 per-out-channel scales."""
    M, K = x8.shape
    Kp, N = wp.shape
    assert Kp * 2 == K, (x8.shape, wp.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0 and bk % 2 == 0
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="int4_matmul",
    )(x8, wp, s_a.reshape(1, 1), s_w)


@functools.partial(jax.jit, static_argnames=("act", "bm", "bn", "bk",
                                             "out_dtype", "interpret"))
def int4_matmul_fused_pallas(x8: jax.Array, wp: jax.Array, s_a: jax.Array,
                             s_w: jax.Array, bias: jax.Array, *,
                             act: str = "none", bm=DEFAULT_BM, bn=DEFAULT_BN,
                             bk=DEFAULT_BK, out_dtype=jnp.float32,
                             interpret: bool = False) -> jax.Array:
    """Fused decode path: int4 matmul + dequant + bias + activation epilogue.

    Same operands as :func:`int4_matmul_pallas` plus ``bias: (1, N) f32`` and
    a static ``act`` ('none' | 'gelu' | 'relu'). The epilogue runs in f32, so
    for f32 outputs the result is bit-identical to the unfused composition
    (matmul kernel -> +bias -> act_fn) while writing the (M, N) intermediate
    to HBM exactly once instead of three times.
    """
    M, K = x8.shape
    Kp, N = wp.shape
    assert Kp * 2 == K, (x8.shape, wp.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0 and bk % 2 == 0
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_fused_kernel, n_k=n_k, act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="int4_matmul_fused",
    )(x8, wp, s_a.reshape(1, 1), s_w, bias)
