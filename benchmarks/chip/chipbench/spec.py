"""Resolve a cell of ``BENCHMARK.json`` to its files, by name."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]      # benchmarks/chip
REPO = BENCH_DIR.parents[1]                          # root of the checkout


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload entry with everything it names, loaded."""

    name: str
    entry: dict               # the workloads[] entry
    config: dict              # configs/<config>.json
    config_entry: dict        # the configs[] entry
    traffic: dict             # traffic/<traffic>.json
    end_to_end: list          # metric entries this cell reports (trace 0)
    per_layer: list           # metric entries this cell reports (trace 1)
    repo: Path
    bench_dir: Path

    def reader(self, metric_name: str):
        """``read(ctx)`` of ``metrics/<stem>.py``, the stem being the part
        of the metric's name before the first '.'."""
        stem = metric_name.split(".")[0]
        mod = _load_module(self.bench_dir / "metrics" / f"{stem}.py",
                           f"chipbench_metric_{stem}")
        return mod.read

    def reference(self):
        family = self.config["family"]
        return _load_module(self.bench_dir / "references" / f"{family}.py",
                            f"chipbench_reference_{family}")


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, repo: Path = REPO,
              bench_dir: Path = BENCH_DIR) -> Cell:
    manifest = json.loads((repo / "BENCHMARK.json").read_text())
    entries = {w["name"]: w for w in manifest["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg_entry = configs[entry["config"]]
    config = json.loads((repo / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{entry['traffic']}.json").read_text())
    e2e = [m for m in manifest["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(name=name, entry=entry, config=config, config_entry=cfg_entry,
                traffic=traffic, end_to_end=e2e, per_layer=layer, repo=repo,
                bench_dir=bench_dir)


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The published peaks of ``device_kind``; a device not in the table is
    an error, never a default."""
    table = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[device_kind]
