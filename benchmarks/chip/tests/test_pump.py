"""Latencies are stamped from the due time: a stalled step shows in the
wait of every request that fell due during it, and a request still waiting
when the window closes enters the tail at its wait so far."""
import importlib.util
import time
from pathlib import Path

import numpy as np

from chipbench import pump as P
from chipbench.traffic import Arrival

METRICS = Path(__file__).resolve().parents[1] / "metrics"


def reader(stem):
    spec = importlib.util.spec_from_file_location(
        f"m_{stem}", METRICS / f"{stem}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Sched:
    def __init__(self):
        self.queue = []

    @property
    def has_work(self):
        return bool(self.queue)


class StallingEngine:
    """Answers every queued encode request in the next step; the step at
    ``stall_at`` sleeps ``stall_s`` first."""

    def __init__(self, stall_at=3, stall_s=0.25, hold=()):
        self.scheduler = _Sched()
        self.steps = 0
        self.stall_at, self.stall_s, self.hold = stall_at, stall_s, hold
        self.rid = 0

    def submit_encode(self, req, on_result):
        self.rid += 1
        req.rid = self.rid
        self.scheduler.queue.append((req, on_result))

        class H:
            rid = self.rid
        return H()

    def engine_step(self):
        self.steps += 1
        if self.steps == self.stall_at:
            time.sleep(self.stall_s)
        keep = []
        for req, cb in self.scheduler.queue:
            if req.rid in self.hold:
                keep.append((req, cb))
            else:
                cb(req.rid, np.zeros(2, np.float32))
        self.scheduler.queue = keep
        return []

    def pop_done(self):
        return []


class Ctx:
    def __init__(self, reqs, window):
        self.reqs, self.window = reqs, window


def _arrivals(n, gap):
    return [Arrival(i, i * gap, np.ones(4, np.int32)) for i in range(n)]


def test_stall_shows_in_the_tail():
    eng = StallingEngine(stall_at=3, stall_s=0.25)
    pump = P.Pump(eng, "classify")
    arr = _arrivals(40, 0.01)
    t0 = P.CLOCK()
    pump.run_open(arr, t0, t0 + 0.6)
    w = np.asarray(P.waits(pump.reqs, (t0, t0 + 0.6)))
    # about half of the requests fell due inside the 250 ms stall
    assert np.percentile(w, 95) >= 0.1
    late = [r.submit_t - r.due for r in pump.reqs]
    assert max(late) >= 0.2          # the generator reports its lateness


def test_stall_across_the_close_shows():
    """A step that starts before the window closes and ends after it: the
    requests that fell due meanwhile are offered, counted and enter the
    tail at their wait so far."""
    class LateStall(StallingEngine):
        def engine_step(self):
            if not self.steps and P.CLOCK() >= self.t_stall:
                self.steps = 1
                time.sleep(0.25)
            for req, cb in self.scheduler.queue:
                cb(req.rid, np.zeros(2, np.float32))
            self.scheduler.queue = []
            return []
    eng = LateStall()
    pump = P.Pump(eng, "classify")
    arr = _arrivals(40, 0.01)
    t0 = P.CLOCK()
    eng.t_stall, t1 = t0 + 0.2, t0 + 0.3
    nxt = pump.run_open(arr, t0, t1)
    assert nxt == 30 == len(pump.reqs)          # every arrival due by t1
    assert P.CLOCK() >= t1 + 0.1                # the stall crossed the close
    w = np.asarray(P.waits(pump.reqs, (t0, t1)))
    assert len(w) == 30
    # ten requests fell due in the last 100 ms and were not answered by t1
    assert sum(1 for r in pump.reqs if not r.done) >= 9
    assert np.percentile(w, 95) >= 0.06


def test_unanswered_enters_at_its_wait():
    eng = StallingEngine(stall_at=0, hold={1})
    pump = P.Pump(eng, "classify")
    t0 = P.CLOCK()
    pump.run_open(_arrivals(1, 0.0), t0, t0 + 0.3)
    assert not pump.reqs[0].done
    lat = reader("encode_p50_ms")(Ctx(pump.reqs, (t0, t0 + 0.3)))
    assert 250.0 <= lat <= 350.0


def test_rejected_counts_as_failed():
    class Refusing(StallingEngine):
        def submit_encode(self, req, on_result):
            raise ValueError("input exceeds max_len")
    pump = P.Pump(Refusing(), "classify")
    t0 = P.CLOCK()
    pump.run_open(_arrivals(3, 0.0), t0, t0 + 0.05)
    assert all(r.failed for r in pump.reqs)
    assert reader("encode_p50_ms")(Ctx(pump.reqs, (t0, t0 + 0.05))) is None
