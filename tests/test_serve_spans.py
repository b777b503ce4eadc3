"""Host spans and counters of the serving engine (DESIGN.md §7).

``ServeMetrics.span`` rolls each span into plain per-name counters (count,
total, self time, longest) stamped on the recorder's injected clock, and
writes the same span into any active ``jax.profiler`` trace. The engine
opens spans at its layer boundaries (``serve/step``, ``serve/admit``,
``serve/encode/*``, ``serve/prefill/*``, ``serve/decode/*``,
``serve/sample/readback``) and counts the useful and computed tokens and
the outputs read back of every encode group. On a ``VirtualClock`` every number here is exact: the
clock moves only where a test moves it.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.policy import QuantPolicy
from repro.deploy import ExecutionPlan, deploy
from repro.models import api
from repro.models.bert import init_bert_classifier, tinybert_config
from repro.serving import (EncodeRequest, GenerationRequest, ServeMetrics,
                           ServingEngine, VirtualClock)

KEY = jax.random.PRNGKey(0)
DISPATCH_S = 0.001       # virtual seconds one jitted encode call takes
READBACK_S = 0.002       # virtual seconds one output's host copy takes
N_OUT = 1                # classify-only groups copy the classify head alone
LENS = (5, 9, 17)        # buckets 8, 16, 32: three groups of one row


# ------------------------------------------------------------- recorder

def test_nested_spans_exact_counters_and_drain():
    clock = VirtualClock()
    m = ServeMetrics(clock=clock)
    with m.span("outer", k=1):
        clock.advance(0.5)
        for dt in (2.0, 3.0):
            with m.span("inner"):
                clock.advance(dt)
        clock.advance(1.0)
    m.count("tokens", 7)
    m.count("tokens", 5)
    s = m.summary()["spans"]
    assert s["outer"] == {"n": 1, "s": 6.5, "self_s": 1.5, "max_s": 6.5}
    assert s["inner"] == {"n": 2, "s": 5.0, "self_s": 5.0, "max_s": 3.0}
    assert "outer: 1x mean 6500.000ms max 6500.000ms" in m.report()
    out = m.pop_summary()
    assert out["tokens"] == 12 and set(out["spans"]) == {"outer", "inner"}
    drained = m.pop_summary()
    assert "spans" not in drained and "tokens" not in drained


def test_span_open_across_drain_closes_into_new_window():
    clock = VirtualClock()
    m = ServeMetrics(clock=clock)
    with m.span("outer"):
        with m.span("inner"):
            clock.advance(1.0)
        m.pop_summary()
        clock.advance(2.0)
    s = m.pop_summary()["spans"]
    assert s == {"outer": {"n": 1, "s": 3.0, "self_s": 2.0, "max_s": 3.0}}


def test_span_records_when_the_body_raises():
    clock = VirtualClock()
    m = ServeMetrics(clock=clock)
    with pytest.raises(RuntimeError):
        with m.span("outer"):
            clock.advance(1.0)
            raise RuntimeError("boom")
    assert m.summary()["spans"]["outer"]["n"] == 1
    assert m._open == []


# --------------------------------------------------------- encoder engine

_MODEL = {}


def _encoder_model():
    if "m" not in _MODEL:
        cfg = tinybert_config(num_classes=2, layers=2, d=64, heads=4,
                              d_ff=128, vocab=256, name="tinybert-spans")
        pol = QuantPolicy(num_layers=cfg.num_layers, mode="int",
                          last_k_int4=cfg.num_layers)
        plan = ExecutionPlan.build(cfg, pol, backend="reference",
                                   mode="encoder", prefill_batch=4,
                                   act_bits=4)
        _MODEL["m"] = deploy(init_bert_classifier(cfg, 2, KEY), plan)
    return _MODEL["m"]


class _SlowCopy:
    """A device output whose host copy takes ``READBACK_S`` virtual s."""

    def __init__(self, arr, clock):
        self._arr, self._clock = arr, clock

    def __array__(self, dtype=None, copy=None):
        self._clock.advance(READBACK_S)
        return np.asarray(self._arr, dtype)


def _timed_engine():
    """An encoder engine whose forwards move the virtual clock: each jitted
    call takes DISPATCH_S and each output's readback READBACK_S."""
    clock = VirtualClock()
    eng = ServingEngine(_encoder_model(), slots=4, max_len=64, clock=clock)
    real = eng._encode_fn

    def timed_fn(bucket, n):
        fn = real(bucket, n)

        def call(*a):
            out = fn(*a)
            clock.advance(DISPATCH_S)
            return {k: _SlowCopy(v, clock) for k, v in out.items()}
        return call
    eng._encode_fn = timed_fn
    return eng


@pytest.fixture(scope="module")
def stepped():
    """One engine_step over three requests of LENS tokens."""
    eng = _timed_engine()
    rng = np.random.default_rng(0)
    hs = [eng.submit_encode(EncodeRequest(
        tokens=rng.integers(1, 256, n).astype(np.int32), task="classify"))
        for n in LENS]
    eng.engine_step()
    assert all(h.finish_reason == "done" for h in hs)
    return eng.metrics.summary()


def test_encoder_step_nests_spans_once_per_group(stepped):
    sp = stepped["spans"]
    groups = len(LENS)
    assert sp["serve/step"]["n"] == 1 and sp["serve/admit"]["n"] == 1
    for name in ("group", "pack", "dispatch", "readback", "finalize"):
        assert sp[f"serve/encode/{name}"]["n"] == groups, name
    assert sp["serve/encode/dispatch"]["s"] == pytest.approx(
        groups * DISPATCH_S)
    assert sp["serve/encode/readback"]["s"] == pytest.approx(
        groups * N_OUT * READBACK_S)
    assert sp["serve/encode/readback"]["max_s"] == pytest.approx(
        N_OUT * READBACK_S)
    # all the clock moved lies in the children: the group and the step
    # hold them (zero self time), and the step holds the groups
    group = sp["serve/encode/group"]
    assert group["s"] == pytest.approx(groups * (DISPATCH_S
                                                 + N_OUT * READBACK_S))
    assert group["self_s"] == pytest.approx(0.0, abs=1e-12)
    assert sp["serve/step"]["s"] == pytest.approx(group["s"])
    assert sp["serve/step"]["self_s"] == pytest.approx(0.0, abs=1e-12)


def test_encode_token_counters_match_a_hand_count(stepped):
    assert stepped["encode_tokens_useful"] == sum(LENS) == 31
    assert stepped["encode_tokens_computed"] == 8 + 16 + 32 == 56
    assert stepped["encode_arrays_read"] == len(LENS) * N_OUT == 3


def test_encode_step_sample_unchanged(stepped):
    """One 'encode' sample per group, from before the inputs' transfer to
    after the outputs' readback, carrying the group's useful tokens."""
    assert stepped["encode_steps"] == len(LENS)
    assert stepped["encode_tokens"] == sum(LENS)
    assert stepped["encode_mean_ms"] == pytest.approx(
        (DISPATCH_S + N_OUT * READBACK_S) * 1e3)


def test_encode_latency_wait_stream_removed(stepped):
    assert not any(k.startswith("encode_latency") for k in stepped)
    assert stepped["queue_wait_n"] == len(LENS)


def test_readback_span_nests_in_step_on_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    eng = ServingEngine(_encoder_model(), slots=4, max_len=64,
                        clock=VirtualClock())
    eng.submit_encode(EncodeRequest(tokens=np.arange(1, 10, dtype=np.int32)))
    eng.engine_step()                        # compile outside the trace
    eng.submit_encode(EncodeRequest(tokens=np.arange(1, 10, dtype=np.int32)))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.engine_step()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve/"):
                    found[ev.name] = (plane.name, ev.start_ns, ev.end_ns,
                                      {k: v for k, v in ev.stats})
    assert {"serve/step", "serve/admit", "serve/encode/group",
            "serve/encode/pack", "serve/encode/dispatch",
            "serve/encode/readback", "serve/encode/finalize"} <= set(found)
    step_plane, s0, s1, _ = found["serve/step"]
    rb_plane, r0, r1, _ = found["serve/encode/readback"]
    assert rb_plane == step_plane and step_plane.startswith("/host:")
    assert s0 <= r0 <= r1 <= s1
    assert found["serve/encode/group"][3] == {"bucket": 16, "rows": 1,
                                             "useful": 9}


# ---------------------------------------------------------- decode engine

def _decoder_engine(prefill_mode, prefix_cache=0):
    cfg = reduced(get_config("stablelm-3b"))
    pol = QuantPolicy(num_layers=cfg.num_layers, mode="int",
                      last_k_int4=cfg.num_layers)
    plan = ExecutionPlan.build(cfg, pol, backend="reference",
                               prefill_mode=prefill_mode,
                               prefix_cache=prefix_cache)
    return ServingEngine(deploy(api.init_model(cfg, KEY), plan), slots=2,
                         max_len=64)


@pytest.mark.parametrize("prefill_mode,prefix_cache,want", [
    ("chunked", 0, ("serve/prefill/pack", "serve/prefill/dispatch",
                    "serve/prefill/readback", "serve/sample/readback",
                    "serve/decode/dispatch", "serve/decode/readback")),
    ("chunked", 1 << 20, ("serve/prefill/pack", "serve/prefill/dispatch",
                          "serve/prefill/readback", "serve/sample/readback",
                          "serve/decode/dispatch", "serve/decode/readback")),
    ("token", 0, ("serve/decode/dispatch", "serve/decode/readback")),
])
def test_decode_paths_record_their_spans(prefill_mode, prefix_cache, want):
    eng = _decoder_engine(prefill_mode, prefix_cache)
    prompt = np.arange(1, 6, dtype=np.int32)
    eng.submit(GenerationRequest(prompt=prompt, max_new_tokens=3))
    steps = eng.run_until_drained()
    s = eng.metrics.summary()
    sp = s["spans"]
    assert sp["serve/step"]["n"] == steps
    assert set(want) <= set(sp)
    assert not any(k.startswith("serve/encode") for k in sp)
    # one decode readback per decode sample; every span nests inside a
    # step, so the self times of all of them add up to the steps' time
    assert sp["serve/decode/readback"]["n"] == s["decode_steps"]
    assert sum(c["self_s"] for c in sp.values()) == pytest.approx(
        sp["serve/step"]["s"])
