"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.calibration import weight_scale
from repro.core.packing import pack_int4, quantize_weight, unpack_int4
from repro.kernels import ops, ref
from repro.kernels.act_quant import act_quant_pallas
from repro.kernels.int4_matmul import int4_matmul_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas

SHAPES = [(8, 16, 8), (32, 64, 48), (128, 256, 128), (64, 512, 256),
          (256, 128, 384), (16, 1024, 64)]


def _mk(m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32) * 0.05)
    return x, w


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_int8_matmul_sweep(m, k, n):
    x, w = _mk(m, k, n, seed=m + k)
    s_w = weight_scale(w, 8, axis=1)
    w8 = jnp.round(jnp.clip(w / s_w, -127, 127)).astype(jnp.int8)
    s_a = jnp.float32(float(jnp.max(jnp.abs(x))) / 127)
    out = ops.int8_matmul(x, w8, s_a, s_w)
    x8 = ref.act_quant_ref(x, s_a, 8)
    exp = ref.int8_matmul_ref(x8, w8, s_a, s_w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_int4_matmul_sweep(m, k, n):
    x, w = _mk(m, k, n, seed=m + n)
    s_w = weight_scale(w, 4, axis=1)
    wp, _ = quantize_weight(w, s_w, 4)
    s_a = jnp.float32(float(jnp.max(jnp.abs(x))) / 8)
    out = ops.int4_matmul(x, wp, s_a, s_w, a_bits=4)
    x4 = ref.act_quant_ref(x, s_a, 4)
    exp = ref.int4_matmul_ref(x4, wp, s_a, s_w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dim,target,align,want", [
    (100, 128, 8, (100, 100)),      # fits: the whole axis is one block
    (200, 128, 8, (40, 200)),       # aligned divisor, no padding
    (6912, 512, 128, (384, 6912)),  # stablelm-3b d_ff as K
    (1200, 128, 128, (128, 1280)),  # tinybert4 d_ff as N: no divisor, pad
    (1200, 512, 128, (256, 1280)),
    (203, 128, 8, (104, 208))])
def test_tile_choice(dim, target, align, want):
    block, padded = ops._tile(dim, target, align)
    assert (block, padded) == want
    assert padded % block == 0 and (block == dim or block % align == 0)


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("m,k,n", [(200, 1200, 312), (13, 6912, 40),
                                   (136, 600, 1200)])
def test_padded_tiles_bit_identical(m, k, n, bits):
    """Shapes whose tiles need zero-code padding give exactly the unpadded
    integer product."""
    x, w = _mk(m, k, n, seed=m + k + n)
    s_w = weight_scale(w, bits, axis=1)
    s_a = jnp.float32(float(jnp.max(jnp.abs(x))) / (8 if bits == 4 else 127))
    xq = ref.act_quant_ref(x, s_a, bits)
    if bits == 4:
        wp, _ = quantize_weight(w, s_w, 4)
        out = ops.int4_matmul(x, wp, s_a, s_w, a_bits=4)
        exp = ref.int4_matmul_ref(xq, wp, s_a, s_w)
    else:
        w8 = jnp.round(jnp.clip(w / s_w, -127, 127)).astype(jnp.int8)
        out = ops.int8_matmul(x, w8, s_a, s_w)
        exp = ref.int8_matmul_ref(xq, w8, s_a, s_w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_interpret_only_on_cpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="interpreted on CPU only"):
        ops._interpret()


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("m,k", [(8, 16), (64, 128), (256, 96)])
def test_act_quant_sweep(m, k, bits):
    rng = np.random.default_rng(m * k + bits)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32) * 3)
    s = jnp.float32(0.1)
    out = act_quant_pallas(x, s, bits=bits, bm=min(8, m), interpret=True)
    exp = ref.act_quant_ref(x, s, bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_out_dtypes(dtype):
    x, w = _mk(64, 128, 64)
    s_w = weight_scale(w, 8, axis=1)
    w8 = jnp.round(jnp.clip(w / s_w, -127, 127)).astype(jnp.int8)
    s_a = jnp.float32(0.05)
    out = int8_matmul_pallas(ref.act_quant_ref(x, s_a, 8), w8, s_a, s_w,
                             out_dtype=dtype, interpret=True)
    assert out.dtype == dtype


def test_block_shape_variants():
    """BlockSpec tilings must not change results."""
    x, w = _mk(128, 256, 128, seed=7)
    s_w = weight_scale(w, 4, axis=1)
    wp, _ = quantize_weight(w, s_w, 4)
    s_a = jnp.float32(0.07)
    x4 = ref.act_quant_ref(x, s_a, 4)
    exp = ref.int4_matmul_ref(x4, wp, s_a, s_w)
    for bm, bn, bk in [(32, 32, 64), (64, 128, 128), (128, 64, 256)]:
        out = int4_matmul_pallas(x4, wp, s_a, s_w.reshape(1, -1), bm=bm,
                                 bn=bn, bk=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=1e-5, atol=1e-5)


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(1)
    codes = jnp.asarray(rng.integers(-7, 9, size=(64, 32)).astype(np.int8))
    packed = pack_int4(codes, axis=0)
    assert packed.shape == (32, 32) and packed.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed, axis=0)),
                                  np.asarray(codes))
    # stacked (layers/experts) packing along K = axis -2
    codes3 = jnp.asarray(rng.integers(-7, 9, size=(3, 10, 6)).astype(np.int8))
    packed3 = pack_int4(codes3, axis=-2)
    assert packed3.shape == (3, 5, 6)
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed3, axis=-2)),
                                  np.asarray(codes3))


def test_int4_hbm_bytes_are_half_of_int8():
    """The deployment asset: packed int4 weights move half the bytes."""
    w = jnp.zeros((512, 256))
    s = jnp.ones((1, 256))
    wp, _ = quantize_weight(w, s, 4)
    w8, _ = quantize_weight(w, s, 8)
    assert wp.size * wp.dtype.itemsize * 2 == w8.size * w8.dtype.itemsize


@pytest.mark.parametrize("shape", [(2, 64, 4, 2, 16, 16, 16, True),
                                   (1, 128, 8, 8, 32, 32, 16, True),
                                   (2, 64, 4, 4, 16, 32, 16, False),
                                   (1, 256, 4, 1, 64, 64, 64, True)])
def test_flash_attention_sweep(shape):
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.models.attention import _repeat_kv, full_attention
    B, S, H, Hkv, dh, bq, bk, causal = shape
    ks = jax.random.split(jax.random.PRNGKey(B * S), 3)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, dh))
    out = flash_attention_pallas(q, k, v, causal=causal, bq=bq, bk=bk,
                                 interpret=True)
    ref = full_attention(q, _repeat_kv(k, H // Hkv), _repeat_kv(v, H // Hkv),
                         causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_flash_attention_bf16():
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.models.attention import full_attention
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 16), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 64, 2, 16), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 64, 2, 16), jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, causal=True, bq=16, bk=16,
                                 interpret=True)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)
