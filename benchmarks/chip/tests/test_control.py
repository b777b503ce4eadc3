"""The control of the classify check, at a size a test holds: the plain
reference computed in bfloat16, put in the program's place, must come out
not correct against the committed limit. Twelve layers are kept (depth is
what lets one flipped 4-bit code grow into a different answer); the widths
are cut to a quarter so that the CPU holds it in interpret mode."""
import copy
import io
import json

from conftest import BENCH, TINY_BERT, TINY_TRAFFIC, make_bench
from test_run_cpu import PEAKS

from chipbench import runner, spec

REAL = json.loads((BENCH / "configs" / "bert-base-w4a4.json").read_text())


def test_bf16_control_fails(tmp_path):
    cfg = copy.deepcopy(TINY_BERT)
    cfg["program_overrides"].update(num_layers=12, d_model=256, d_ff=1024)
    cfg.update(num_hidden_layers=12, hidden_size=256, intermediate_size=1024)
    cfg["plan"]["last_k_int4"] = 12
    cfg["check"] = copy.deepcopy(REAL["check"])
    cfg["check"]["sample"] = 16
    bench = make_bench(tmp_path, cfg, TINY_TRAFFIC)
    cell = spec.load_cell("tiny-bert.classify", repo=tmp_path,
                          bench_dir=bench)
    res = runner.run(cell, 2 ** 31 + 77, 1.5, False, runner.pump_mod.CLOCK(),
                     require_tpu=False, out=io.StringIO(), peaks=PEAKS,
                     control="bfloat16")
    assert not res["correct"]
    c = res["checks"]["median_dev"]
    assert c["limit"] == REAL["check"]["limits"]["median_dev"]
    assert c["value"] > c["limit"]
