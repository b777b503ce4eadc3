"""BENCHMARK.json against the contract's characters and keys; every name
resolves to its files; new configurations, mixes and metrics resolve when
dropped in as files; and without a TPU the command fails and prints no
result."""
import json
import os
import re
import subprocess
import sys

from chipbench import spec

from conftest import BENCH, REPO, TINY_BERT, TINY_TRAFFIC, make_bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
M = json.loads((REPO / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmarks/chip"]
    assert 1 <= M["run_seconds"] <= 51


def test_names_units_and_keys():
    names = set()
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/chip/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in M["end_to_end"] + M["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in M["end_to_end"]}
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and "\n" not in m["layer"]


def test_every_name_resolves():
    for w in M["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cell.reader(m["name"]))
        assert cell.reference().COMPARED
    for m in M["per_layer"]:     # each listed cell reports what it moves
        for w in m.get("workloads", []):
            assert any(e["name"] == m["moves"]
                       for e in spec.load_cell(w).end_to_end)


def test_files_dropped_in_resolve(tmp_path):
    bench = make_bench(tmp_path, TINY_BERT, TINY_TRAFFIC,
                       cell="new-model.new-mix")
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    man = json.loads((tmp_path / "BENCHMARK.json").read_text())
    man["per_layer"].append({"name": "new_metric.x", "unit": "ms",
                             "better": "lower", "source": "program_span",
                             "layer": "engine", "moves": "setup_s",
                             "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    cell = spec.load_cell("new-model.new-mix", repo=tmp_path,
                          bench_dir=bench)
    assert cell.config["name"] == TINY_BERT["name"]
    assert cell.traffic == TINY_TRAFFIC
    assert cell.reader("new_metric.x")(None) == 42.0
    assert "new_metric.x" in [m["name"] for m in cell.per_layer]


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = M["workloads"][0]["name"]
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        name, "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
