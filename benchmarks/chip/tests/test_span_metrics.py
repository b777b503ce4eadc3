"""Readers of the program's spans and counters, on hand-built slices, and a
traced run of the tiny cell that prints them."""
import types

import pytest
from conftest import TINY_BERT, TINY_TRAFFIC, make_bench
from test_run_cpu import _run

from chipbench import spec

CELL = "bert-base-w4a4.classify"
NAMES = ("engine_host_share.encode_p50", "encode_readback_ms.encode_p50",
         "encode_useful_share.encode_p50")


def _read(name, serve):
    reader = spec.load_cell(CELL).reader(name)
    return reader(types.SimpleNamespace(name=name, serve=serve))


SLICE = {
    "wall_s": 5.0,
    "spans": {"serve/step": {"n": 800, "s": 3.5, "self_s": 0.1,
                             "max_s": 0.12},
              "serve/encode/readback": {"n": 1000, "s": 2.0, "self_s": 2.0,
                                        "max_s": 0.02}},
    "encode_tokens_useful": 45_000,
    "encode_tokens_computed": 64_000,
}


def test_readers_on_a_hand_built_slice():
    assert _read(NAMES[0], SLICE) == pytest.approx(100 * (3.5 - 2.0) / 5.0)
    assert _read(NAMES[1], SLICE) == pytest.approx(2.0)
    assert _read(NAMES[2], SLICE) == pytest.approx(100 * 45 / 64)


@pytest.mark.parametrize("name", NAMES)
def test_readers_return_none_without_spans_or_counters(name):
    """A program without the spans (an older commit) reads nothing."""
    assert _read(name, {"wall_s": 5.0, "encode_steps": 10}) is None


def test_traced_run_reads_span_metrics(tmp_path):
    bench = make_bench(tmp_path, TINY_BERT, TINY_TRAFFIC)
    res = _run(bench, trace=True)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    for name in NAMES:
        assert name in got, sorted(got)
    assert 0 < got["engine_host_share.encode_p50"]["value"] < 100
    assert got["encode_readback_ms.encode_p50"]["value"] > 0
    assert 0 < got["encode_useful_share.encode_p50"]["value"] <= 100
