"""Harness tests: CPU only, small shapes. Run with
``python -m pytest benchmarks/chip/tests`` from the checkout's root."""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: a bert of the program's family at a size the CPU holds in interpret mode
TINY_BERT = {
    "name": "tiny-bert", "family": "bert", "registry": "bert-base",
    "source": "test", "program_overrides": {
        "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
        "d_ff": 128, "vocab_size": 1000},
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "intermediate_size": 128, "vocab_size": 1000, "hidden_act": "gelu",
    "num_labels": 2,
    "plan": {"last_k_int4": 2, "act_bits": 4, "kv_bits": 16,
             "backend": "pallas", "mode": "encoder"},
    "engine": {"slots": 8, "max_len": 32, "prefill_batch": 2},
    "calibration": {"batches": 2, "batch": 2, "seq": 16,
                    "percentile": 99.99},
    "init_std": 0.02,
    "check": {"sample": 8, "limits": {"median_dev": 0.01, "mean_dev": 0.03}},
}
TINY_TRAFFIC = {"loop": "open", "task": "classify", "rate_rps": 20,
                "ramp_s": 0.2,
                "prompt_len": {"dist": "lognormal", "median": 8,
                               "sigma": 0.6, "min": 9, "max": 16}}


def make_bench(tmp: Path, config: dict, traffic: dict,
               cell: str = "tiny-bert.classify") -> Path:
    """A checkout-shaped directory: BENCHMARK.json naming one cell, its
    config and traffic as files, and the real readers and references."""
    bench = tmp / "benchmarks" / "chip"
    for sub in ("configs", "traffic"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "references", "work"):
        shutil.copytree(BENCH / sub, bench / sub, dirs_exist_ok=True)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    cname, tname = cell.split(".", 1)
    (bench / "configs" / f"{cname}.json").write_text(json.dumps(config))
    (bench / "traffic" / f"{tname}.json").write_text(json.dumps(traffic))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    manifest["configs"] = [{"name": cname, "source": "test",
                            "file": f"benchmarks/chip/configs/{cname}.json",
                            "reduced": [], "why": "test"}]
    manifest["workloads"] = [{"name": cell, "config": cname,
                              "traffic": tname, "chips": 1, "why": "test"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [cell]
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return bench


@pytest.fixture(autouse=True, scope="session")
def _own_compile_cache(tmp_path_factory):
    """Tests keep their CPU programs out of the checkout's cache."""
    from chipbench import runner
    runner.CACHE_DIR = tmp_path_factory.mktemp("jax_cache")


@pytest.fixture
def tiny_bench(tmp_path):
    return make_bench(tmp_path, TINY_BERT, TINY_TRAFFIC)
