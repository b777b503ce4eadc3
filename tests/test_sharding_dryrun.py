"""Sharding rules + a small-mesh dry-run (subprocess: needs >1 host device).

The full production dry-run (512 devices, all 40 cells) runs via
``python -m repro.launch.dryrun --all``; here we assert the machinery on an
8-device toy mesh quickly enough for CI.
"""
import json
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import batch_spec, param_specs
from repro.models import api


def _specs_for(arch):
    cfg = get_config(arch)
    params = jax.eval_shape(lambda k: api.init_model(cfg, k),
                            jax.random.PRNGKey(0))
    return param_specs(params)


def test_dense_param_specs():
    specs = _specs_for("qwen2.5-32b")
    assert specs["embed"] == P("model", None)
    assert specs["lm_head"] == P(None, "model")
    assert specs["layers"]["attn"]["wq"]["w"] == P(None, None, "model")
    assert specs["layers"]["attn"]["wq"]["b"] == P(None, "model")
    assert specs["layers"]["attn"]["wo"]["w"] == P(None, "model", None)
    assert specs["layers"]["ffn"]["w1"]["w"] == P(None, None, "model")
    assert specs["layers"]["ffn"]["w2"]["w"] == P(None, "model", None)
    assert specs["layers"]["ln1"]["scale"] == P(None, None)
    # scales follow their weight's out-channel sharding
    assert specs["layers"]["ffn"]["w1"]["s_w"] == P(None, None, "model")
    assert specs["layers"]["ffn"]["w2"]["s_w"] == P(None, None, None)


def test_moe_param_specs():
    specs = _specs_for("qwen2-moe-a2.7b")
    assert specs["layers"]["moe"]["w1"]["w"] == P(None, None, None, "model")
    assert specs["layers"]["moe"]["w2"]["w"] == P(None, None, "model", None)
    assert specs["layers"]["moe"]["router"] == P(None, None, None)


def test_ssm_param_specs():
    specs = _specs_for("zamba2-2.7b")
    assert specs["mamba"]["in_x"]["w"] == P(None, None, None, "model")
    assert specs["mamba"]["out_proj"]["w"] == P(None, None, "model", None)
    assert specs["mamba"]["in_bc"]["w"] == P(None, None, None, None)
    assert specs["shared"]["attn"]["wq"]["w"] == P(None, "model")


def test_batch_spec_axes():
    mesh1 = jax.make_mesh((1, 1), ("data", "model"))
    assert batch_spec(mesh1, 2) == P("data", None)
    mesh2 = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
    assert batch_spec(mesh2, 2) == P(("pod", "data"), None)


SMALL_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
from repro.launch import dryrun
from repro.launch.mesh import make_mesh_for_devices
mesh = make_mesh_for_devices(8, 4).mesh
results = {}
for arch, shape in [("stablelm-3b", "train_4k"), ("stablelm-3b", "decode_32k"),
                    ("granite-moe-3b-a800m", "train_4k")]:
    built, skip = dryrun._build_cell(arch, shape, mesh, policy_kind="mkq50",
                                     distill=False, grad_mode="mse",
                                     extra={"microbatch": 4})
    fn, specs = built
    with mesh:
        compiled = fn.lower(*specs).compile()
    txt = compiled.as_text()
    has_coll = any(op in txt for op in ("all-reduce", "all-gather",
                                        "reduce-scatter"))
    results[f"{arch}/{shape}"] = has_coll
print(json.dumps(results))
"""


@pytest.mark.slow
def test_small_mesh_dryrun_compiles():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SMALL_DRYRUN], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    assert all(results.values()), results  # SPMD collectives present


def test_dryrun_artifacts_schema():
    """The stored dry-run JSONs (deliverable e/g) carry every roofline field."""
    import glob
    paths = glob.glob("experiments/dryrun/*.json")
    if not paths:
        pytest.skip("no dry-run artifacts in this checkout")
    ok = skipped = 0
    meshes = set()
    for p in paths:
        with open(p) as f:
            r = json.load(f)
        meshes.add(r["mesh"])
        if r["status"] == "skipped":
            skipped += 1
            assert "full-attention" in r["reason"]
            continue
        ok += 1
        assert r["chips"] in (256, 512)
        for k in ("compute_s", "memory_s", "collective_s"):
            assert r["roofline_terms_s"][k] >= 0
        assert r["dominant"] in ("compute_s", "memory_s", "collective_s")
        m = r["memory"]
        assert m["total_bytes"] == m["argument_bytes"] + m["temp_bytes"]
        assert r["hlo_analysis"]["flops"] > 0
    assert meshes == {"single", "multi"}
    assert ok >= 60 and skipped >= 16


def test_elastic_resume_roundtrip(tmp_path):
    from repro.checkpoint import CheckpointManager
    from repro.launch.elastic import elastic_resume
    mgr = CheckpointManager(str(tmp_path))
    state = {"params": {"layers": {"ffn": {"w1": {
        "w": jax.numpy.ones((2, 4, 4)),
        "s_w": jax.numpy.ones((2, 1, 4)),
        "s_a": jax.numpy.ones((2,))}}}}}
    mgr.save(5, state)
    restored, step, mesh = elastic_resume(state, mgr, model_parallel=1)
    assert step == 5
    assert mesh.devices.size == len(jax.devices())
    w = restored["params"]["layers"]["ffn"]["w1"]["w"]
    assert w.shape == (2, 4, 4)
