"""Drive the serving engine through its public API and stamp every request.

One process, one thread: the pump submits what is due, then calls
``engine_step()``; when the engine is idle it sleeps until the next
arrival. Every latency is taken from a request's *due* time (when the
schedule says it arrives), not from when the pump got round to submitting
it, so a long step shows in the wait of every request that fell due
during it. The pump also keeps how late it submitted each request.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Optional

import numpy as np

from .traffic import Arrival

CLOCK: Callable[[], float] = time.perf_counter


@dataclasses.dataclass
class Req:
    arrival: Arrival
    due: float                         # absolute CLOCK time
    submit_t: Optional[float] = None
    result: Optional[np.ndarray] = None
    finish_t: Optional[float] = None
    failed: Optional[str] = None

    @property
    def plen(self) -> int:
        return len(self.arrival.tokens)

    @property
    def done(self) -> bool:
        return self.finish_t is not None


def waits(reqs: list, window: tuple) -> list:
    """Seconds from due time to result of every request due in ``window``
    that did not fail; one still waiting at the close counts its wait so
    far."""
    t0, t1 = window
    return [(r.finish_t if r.finish_t is not None and r.finish_t <= t1
             else t1) - r.due
            for r in reqs if t0 <= r.due < t1 and not r.failed]


@dataclasses.dataclass
class Step:
    """One ``engine_step``: its host interval and the prompt lengths it
    encoded (the useful tokens of its forwards)."""

    t0: float
    t1: float
    encode_lens: list = dataclasses.field(default_factory=list)


class Pump:
    def __init__(self, engine, task: str, annotate: bool = False):
        from repro.serving import EncodeRequest
        self._Encode = EncodeRequest
        self.engine = engine
        self.task = task
        self.by_rid: dict = {}
        self.reqs: list[Req] = []
        self.steps: list[Step] = []
        self._cur: Optional[Step] = None
        if annotate:
            from jax.profiler import TraceAnnotation
            self._span = TraceAnnotation
        else:
            self._span = lambda name: contextlib.nullcontext()

    def _on_result(self, rid: int, value) -> None:
        r = self.by_rid[rid]
        r.finish_t = CLOCK()
        if value is None:
            r.failed = "no result (shed or cancelled)"
            return
        r.result = np.asarray(value)
        if self._cur is not None:
            self._cur.encode_lens.append(r.plen)

    # --------------------------------------------------------------- verbs
    def submit(self, arrival: Arrival, due: float) -> Req:
        r = Req(arrival=arrival, due=due)
        self.reqs.append(r)
        with self._span("submit"):
            r.submit_t = CLOCK()
            try:
                h = self.engine.submit_encode(
                    self._Encode(tokens=arrival.tokens, task=self.task),
                    on_result=self._on_result)
                self.by_rid[h.rid] = r
            except Exception as e:          # rejected at submit: a failure
                r.failed = f"{type(e).__name__}: {e}"
                r.finish_t = CLOCK()
        return r

    def step(self) -> Step:
        st = self._cur = Step(t0=CLOCK(), t1=0.0)
        with self._span("engine_step"):
            self.engine.engine_step()
        st.t1 = CLOCK()
        self._cur = None
        self.steps.append(st)
        for req in self.engine.pop_done():   # keep the engine's list short
            r = self.by_rid.get(req.rid)
            reason = getattr(req, "finish_reason", None)
            if r is not None and reason in ("shed", "cancelled"):
                r.failed = f"finished as {reason}"
                r.finish_t = r.finish_t or st.t1
        return st

    @property
    def busy(self) -> bool:
        return self.engine.scheduler.has_work

    # ---------------------------------------------------------------- loops
    def run_open(self, arrivals: list, t_first: float, t_end: float,
                 start: int = 0) -> int:
        """Offer ``arrivals[start:]`` at ``t_first + arrival.t`` until
        ``t_end``; returns the index of the first arrival not offered.

        Every arrival due before ``t_end`` is offered before this returns,
        also those that fell due while the last step ran past ``t_end``:
        they are the requests that waited longest."""
        i, n = start, len(arrivals)
        while True:
            now = CLOCK()
            while (i < n and t_first + arrivals[i].t <= now
                   and t_first + arrivals[i].t < t_end):
                self.submit(arrivals[i], t_first + arrivals[i].t)
                i += 1
            if now >= t_end:
                return i
            if self.busy:
                self.step()
                continue
            nxt = t_first + arrivals[i].t if i < n else t_end
            with self._span("generator"):
                time.sleep(max(0.0, min(nxt, t_end) - CLOCK()))

    def drain(self, deadline: float) -> None:
        """Finish what was submitted (no new arrivals), until ``deadline``."""
        while self.busy and CLOCK() < deadline:
            self.step()
