"""Pallas TPU kernel: fused activation quantization (f32/bf16 -> int codes).

Deploy-time activations are quantized on the fly with the QAT-learned
per-tensor scale (paper: true k-bit activation grids). Fusing the
divide/clamp/round into one VMEM pass halves activation HBM traffic vs
quantize-then-store-f32: the fp activation is read once, the int8 code
written once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 256


def _kernel(x_ref, s_ref, out_ref, *, qmin: int, qmax: int):
    z = x_ref[...].astype(jnp.float32) / s_ref[0, 0]
    z = jnp.clip(jnp.round(z), qmin, qmax)
    out_ref[...] = z.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "interpret"))
def act_quant_pallas(x: jax.Array, s: jax.Array, *, bits: int = 8,
                     bm: int = DEFAULT_BM, interpret: bool = False):
    """x: (M, K) float -> (M, K) int8 codes on the paper's k-bit grid.

    M is arbitrary (serving batches batch x seq rows): ragged M is padded up
    to a multiple of the row block and the pad rows sliced off the result —
    quantization is elementwise per row, so pad rows never leak.
    """
    M, K = x.shape
    from ..core.quantizer import qrange
    qmin, qmax = qrange(bits)
    bm = min(bm, M)
    Mp = M if M % bm == 0 else (M // bm + 1) * bm
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, qmin=qmin, qmax=qmax),
        grid=(Mp // bm,),
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, K), jnp.int8),
        interpret=interpret,
        name="act_quant",
    )(x, s.reshape(1, 1))
    return out[:M] if Mp != M else out
