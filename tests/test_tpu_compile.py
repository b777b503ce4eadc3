"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel through ``repro.kernels.ops`` (its
own tile choice and padding) at real model widths and compiles it with the
TPU compiler against a ``v5e:2x2`` topology description, which needs no
chip. A block Mosaic refuses, or VMEM overuse, fails here at no chip time.

The topology is described inside a module fixture only — never at import —
so every test worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

BERT_BASE = dict(d=768, d_ff=3072)
TINYBERT4 = dict(d=312, d_ff=1200)
STABLELM_3B = dict(d=2560, d_ff=6912)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip: keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture()
def on_chip(topo, monkeypatch):
    """A function that compiles ``fn`` for one described chip through the
    TPU branch of ``ops`` and returns the compiled text."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def compile_text(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return text
    return compile_text


def _int4_shapes(M, K, N):
    return [((M, K), jnp.float32), ((K // 2, N), jnp.uint8), ((), jnp.float32),
            ((1, N), jnp.float32)]


@pytest.mark.parametrize("K,N", [(768, 3072), (3072, 768)])
@pytest.mark.parametrize("act", [None, "gelu"])
def test_int4_matmul_bert_base(on_chip, K, N, act):
    fn = lambda x, wp, s_a, s_w: ops.int4_matmul(x, wp, s_a, s_w, a_bits=4,
                                                 act=act)
    on_chip(fn, *_int4_shapes(1024, K, N))


@pytest.mark.parametrize("K,N", [(312, 1200), (1200, 312)])
def test_int4_matmul_tinybert4_pads_to_lanes(on_chip, K, N):
    fn = lambda x, wp, s_a, s_w: ops.int4_matmul(x, wp, s_a, s_w, a_bits=4,
                                                 act="gelu")
    on_chip(fn, *_int4_shapes(256, K, N))


@pytest.mark.parametrize("M", [4, 200])
def test_int4_matmul_stablelm_w2(on_chip, M):
    K, N = STABLELM_3B["d_ff"], STABLELM_3B["d"]
    on_chip(lambda x, wp, s_a, s_w: ops.int4_matmul(x, wp, s_a, s_w),
            *_int4_shapes(M, K, N))


@pytest.mark.parametrize("M,K,N", [(4, 6912, 2560), (200, 6912, 2560),
                                   (200, 2560, 6912), (200, 1200, 312)])
def test_int8_matmul(on_chip, M, K, N):
    on_chip(ops.int8_matmul, ((M, K), jnp.float32), ((K, N), jnp.int8),
            ((), jnp.float32), ((1, N), jnp.float32))


@pytest.mark.parametrize("M,K", [(8, 768), (200, 768), (1024, 2560),
                                 (1024, 3072), (200, 6912)])
@pytest.mark.parametrize("bits", [4, 8])
def test_act_quant(on_chip, M, K, bits):
    on_chip(lambda x, s: ops.act_quant(x, s, bits=bits),
            ((M, K), jnp.float32), ((), jnp.float32))


@pytest.mark.parametrize("H,dh", [(12, 64), (32, 80)])
@pytest.mark.parametrize("kv_bits", [8, 4])
def test_decode_attention(on_chip, H, dh, kv_bits):
    B, S = 4, 256
    dhp, code = (dh, jnp.int8) if kv_bits == 8 else (dh // 2, jnp.uint8)
    on_chip(ops.decode_attention,
            ((B, H, dh), jnp.float32), ((B, S, H, dhp), code),
            ((B, S, H, dhp), code), ((B, S, H), jnp.float32),
            ((B, S, H), jnp.float32), ((B, H, dh), jnp.float32),
            ((B, H, dh), jnp.float32), ((B,), jnp.int32))


@pytest.mark.parametrize("w_bits", [4, 8])
def test_int_matmuls_ignore_default_matmul_precision(on_chip, w_bits):
    """A caller's ``jax.default_matmul_precision('highest')`` asks for an
    fp32 contraction, which Mosaic refuses for int8 operands: the integer
    dots pin their own precision."""
    M, K, N = 200, 2560, 6912
    if w_bits == 4:
        fn, shapes = (lambda x, wp, s_a, s_w: ops.int4_matmul(
            x, wp, s_a, s_w, act="gelu"), _int4_shapes(M, K, N))
    else:
        fn, shapes = ops.int8_matmul, [
            ((M, K), jnp.float32), ((K, N), jnp.int8), ((), jnp.float32),
            ((1, N), jnp.float32)]
    with jax.default_matmul_precision("highest"):
        on_chip(fn, *shapes)
