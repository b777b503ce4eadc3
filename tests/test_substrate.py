"""Substrate tests: optimizer, schedule, data pipeline, checkpoint manager,
gradient compression, straggler watchdog, elastic helpers."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import lm_batches
from repro.data.synthetic import SyntheticLM
from repro.distributed.compression import compressed_grad_mean
from repro.launch.train import StragglerWatchdog
from repro.optim import adam_init, adam_update, group_for_path, \
    linear_warmup_decay


# ------------------------------------------------------------------ optimizer
def test_adam_param_groups():
    params = {"layers": {"ffn": {"w1": {"w": jnp.ones((4, 4)),
                                        "s_w": jnp.ones((1, 4)),
                                        "s_a": jnp.ones(())}}}}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    groups = {"/".join(str(getattr(p, "key", p)) for p in path):
              group_for_path(path) for path, _ in flat}
    assert groups["layers/ffn/w1/w"] == "weights"
    assert groups["layers/ffn/w1/s_w"] == "weight_scale"
    assert groups["layers/ffn/w1/s_a"] == "act_scale"


def test_adam_converges_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    opt = adam_init(params)
    sched = lambda step: jnp.float32(1.0)
    for _ in range(300):
        grads = {"w": 2 * params["w"]}
        params, opt = adam_update(params, grads, opt,
                                  lr_by_group={"weights": 0.1,
                                               "act_scale": 0.1,
                                               "weight_scale": 0.1},
                                  schedule_fn=sched)
    assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2


def test_scales_stay_positive():
    params = {"s_a": jnp.float32(1e-6)}
    opt = adam_init(params)
    for _ in range(10):
        params, opt = adam_update(params, {"s_a": jnp.float32(1.0)}, opt,
                                  lr_by_group={"weights": 0.1,
                                               "act_scale": 0.5,
                                               "weight_scale": 0.1},
                                  schedule_fn=lambda s: jnp.float32(1.0))
    assert float(params["s_a"]) >= 0.99e-8  # clamp, f32 rounding


def test_schedule_shape():
    f = linear_warmup_decay(100, 0.1)
    assert float(f(jnp.int32(0))) == 0.0
    assert float(f(jnp.int32(10))) == pytest.approx(1.0)
    assert float(f(jnp.int32(55))) == pytest.approx(0.5, abs=1e-2)
    assert float(f(jnp.int32(100))) == pytest.approx(0.0, abs=1e-6)


# ------------------------------------------------------------------ data
def test_lm_data_deterministic_and_sharded():
    a = SyntheticLM(256, 16, 8, seed=3).batch(5)
    b = SyntheticLM(256, 16, 8, seed=3).batch(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    h0 = SyntheticLM(256, 16, 8, seed=3, host_index=0, num_hosts=2).batch(0)
    h1 = SyntheticLM(256, 16, 8, seed=3, host_index=1, num_hosts=2).batch(0)
    assert h0["tokens"].shape == (4, 16)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_lm_data_has_learnable_structure():
    """Markov stream: conditional entropy << vocab entropy."""
    d = SyntheticLM(256, 512, 4, seed=0, branching=4)
    toks = d.batch(0)["tokens"].reshape(-1)
    pairs = {}
    for a, b in zip(toks[:-1], toks[1:]):
        pairs.setdefault(int(a), set()).add(int(b))
    avg_branching = np.mean([len(v) for v in pairs.values()])
    assert avg_branching <= 8  # far below vocab=256


def test_prefetcher():
    it = lm_batches(64, 8, 4, prefetch=True)
    batches = [next(iter(it)) for _ in range(3)]
    assert all(b["tokens"].shape == (4, 8) for b in batches)


# ------------------------------------------------------------------ ckpt
def test_checkpoint_roundtrip_and_keep(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
             "step": jnp.int32(7)}
    for s in (10, 20, 30):
        mgr.save(s, state)
    assert mgr.all_steps() == [20, 30]
    restored, step = mgr.restore(state)
    assert step == 30
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(state["params"]["w"]))


def test_checkpoint_crash_safety(tmp_path):
    """A half-written temp dir must not shadow the last good step."""
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = {"w": jnp.ones(3)}
    mgr.save(1, state)
    os.makedirs(os.path.join(str(tmp_path), ".tmp_crashed"), exist_ok=True)
    restored, step = mgr.restore(state)
    assert step == 1 and restored is not None


def test_checkpoint_missing_dir_resume(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    restored, step = mgr.restore({"w": jnp.ones(2)})
    assert restored is None and step is None


# ------------------------------------------------------------------ watchdog
def test_straggler_watchdog():
    w = StragglerWatchdog(factor=3.0)
    for _ in range(10):
        assert not w.observe(0, 1.0)
    assert w.observe(11, 10.0)
    assert w.flagged


# ------------------------------------------------------------------ compression
def test_int8_error_feedback_compression():
    """shard_map int8+EF reduction: mean error -> 0 over repeated steps."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs.reshape(1), ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(0)
                          .standard_normal((4, 8)).astype(np.float32))}

    @jax.jit
    def reduce_once(grads, err):
        def f(gr, er):
            return compressed_grad_mean(gr, ("data",), "int8_ef", er)
        return shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                         out_specs=(P("data"), P("data")))(
            jax.tree.map(lambda a: a[None], grads),
            jax.tree.map(lambda a: a[None], err))

    err = jax.tree.map(jnp.zeros_like, g)
    total_exact = jnp.zeros_like(g["w"])
    total_comp = jnp.zeros_like(g["w"])
    for i in range(50):
        mean, err_ = reduce_once(g, err)
        err = jax.tree.map(lambda a: a[0], err_)
        total_comp = total_comp + mean["w"][0]
        total_exact = total_exact + g["w"]
    # error feedback: accumulated compressed sum tracks the exact sum
    rel = float(jnp.max(jnp.abs(total_comp - total_exact))
                / jnp.max(jnp.abs(total_exact)))
    assert rel < 0.02, rel


def test_bf16_compression_close():
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental.shard_map import shard_map
    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs.reshape(1), ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(1)
                          .standard_normal((16,)).astype(np.float32))}

    def f(gr):
        m, _ = compressed_grad_mean(gr, ("data",), "bf16")
        return m
    out = shard_map(f, mesh=mesh, in_specs=(P("data"),),
                    out_specs=P("data"))(jax.tree.map(lambda a: a[None], g))
    np.testing.assert_allclose(np.asarray(out["w"][0]), np.asarray(g["w"]),
                               rtol=1e-2, atol=1e-2)


# ------------------------------------------------------------------ elastic
def test_elastic_rebalance():
    from repro.launch.elastic import rebalance_batch
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    assert rebalance_batch(256, mesh) == 256


# --------------------------------------------------------------- compile cache
@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(env_dir, tmp_path, monkeypatch):
    """A set JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the cache
    goes to the fixed in-checkout path, the same on every call."""
    from repro.launch import compile_cache as cc
    old = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv(cc.ENV, str(tmp_path / env_dir))
    else:
        monkeypatch.delenv(cc.ENV, raising=False)
    try:
        got = cc.enable_compile_cache()
        if env_dir:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == old  # untouched
        else:
            assert got == str(cc.DEFAULT_DIR) == cc.enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == got
            assert (cc.DEFAULT_DIR.parent / "pyproject.toml").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
