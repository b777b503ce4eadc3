"""The whole run, on the CPU at a tiny size, with the chip look skipped:
a sound program comes out correct, and broken ones do not."""
import copy
import io
import json

from conftest import TINY_BERT, TINY_TRAFFIC, make_bench

from chipbench import runner, spec

PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11,
         "hbm_bytes": 1e9}


def _run(bench, trace=False, seed=123456789012):
    cell = spec.load_cell("tiny-bert.classify", repo=bench.parents[1],
                          bench_dir=bench)
    out = io.StringIO()
    res = runner.run(cell, seed, 1.5, trace, runner.pump_mod.CLOCK(),
                     require_tpu=False, out=out, peaks=PEAKS)
    line = out.getvalue().strip().splitlines()[-1]
    assert json.loads(line) == json.loads(json.dumps(res))
    return res


def test_sound_run_is_correct(tiny_bench):
    res = _run(tiny_bench)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "encode_p50_ms"}
    assert list(res)[-1] == "checks"


def test_answer_altered_fails(tiny_bench, monkeypatch):
    """A fault planted where the answer is produced: each classify result
    is swapped with its neighbour's in the group."""
    from repro.serving import engine as eng
    real = eng.ServingEngine._encode_fn

    def broken(self, bucket, n):
        fn = real(self, bucket, n)

        def wrapped(*a):
            out = dict(fn(*a))
            out["classify"] = out["classify"][::-1] * -1.0
            return out
        return wrapped
    monkeypatch.setattr(eng.ServingEngine, "_encode_fn", broken)
    res = _run(tiny_bench)
    assert not res["correct"]
    assert res["checks"]["median_dev"]["value"] > \
        res["checks"]["median_dev"]["limit"]


def test_longest_bucket_altered_fails(tmp_path, monkeypatch):
    """A fault in a minority of the answers: only the longest bucket's
    answers are negated. The median stays at zero; the mean sees it."""
    from repro.serving import engine as eng
    mix = copy.deepcopy(TINY_TRAFFIC)
    mix["prompt_len"] = {"dist": "lognormal", "median": 6, "sigma": 0.5,
                         "min": 4, "max": 16}
    cfg = copy.deepcopy(TINY_BERT)
    cfg["check"]["sample"] = 16
    bench = make_bench(tmp_path, cfg, mix)
    real = eng.ServingEngine._encode_fn

    def broken(self, bucket, n):
        fn = real(self, bucket, n)
        if bucket < 16:
            return fn

        def wrapped(*a):
            out = dict(fn(*a))
            out["classify"] = out["classify"] * -1.0
            return out
        return wrapped
    monkeypatch.setattr(eng.ServingEngine, "_encode_fn", broken)
    res = _run(bench)
    c = res["checks"]
    assert not res["correct"]
    assert c["median_dev"]["value"] <= c["median_dev"]["limit"]
    assert c["mean_dev"]["value"] > c["mean_dev"]["limit"]
