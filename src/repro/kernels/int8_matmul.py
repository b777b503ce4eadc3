"""Pallas TPU kernel: int8 x int8 -> int32 matmul with fused dequant epilogue.

The Q8BERT-style baseline layer (paper Table 2 'int8' column), TPU-native:
int8 operands feed the MXU (int8xint8->int32), accumulation lives in a VMEM
scratch, and the per-output-channel dequant (s_a * s_w[n]) is fused into the
epilogue on the last K step — the accumulator never round-trips HBM.

Grid: (M/bm, N/bn, K/bk), K innermost so the (bm, bn) scratch accumulates
across K steps. Default blocks are MXU-aligned (128, 128) tiles with a
512-deep K slab: VMEM = bm*bk + bk*bn (int8) + bm*bn*4 (scratch) = 192 KiB,
well under the ~16 MiB/core budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512
# int8 x int8 -> int32 is exact at any precision, but Mosaic refuses the
# fp32 contraction a caller's jax.default_matmul_precision('highest') asks
# for: pin the integer dots to the default
INT_DOT = jax.lax.Precision.DEFAULT


def _kernel(x_ref, w_ref, sa_ref, sw_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        precision=INT_DOT, preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        scale = sa_ref[0, 0] * sw_ref[...]        # () * (1, bn) f32
        out_ref[...] = (acc_ref[...].astype(jnp.float32) * scale
                        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def int8_matmul_pallas(x8: jax.Array, w8: jax.Array, s_a: jax.Array,
                       s_w: jax.Array, *, bm=DEFAULT_BM, bn=DEFAULT_BN,
                       bk=DEFAULT_BK, out_dtype=jnp.float32,
                       interpret: bool = False) -> jax.Array:
    """x8: (M, K) int8, w8: (K, N) int8, s_a: () f32, s_w: (1, N) f32."""
    M, K = x8.shape
    K2, N = w8.shape
    assert K == K2, (x8.shape, w8.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="int8_matmul",
    )(x8, w8, s_a.reshape(1, 1), s_w)
