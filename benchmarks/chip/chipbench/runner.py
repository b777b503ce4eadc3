"""One run of one cell: set-up, window, check, result line."""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path
from typing import Optional

import numpy as np

from . import pump as pump_mod
from .spec import Cell

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
DRAIN_S = 60.0          # how long a due answer may come after the window


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, whatever
    the environment says, so only a checkout's first run compiles."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CACHE_DIR)


class GcClock:
    """Pauses of Python's cyclic collector, for the log: a long one shows
    as a long step on the host."""

    def __init__(self):
        self.pauses: list = []
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = pump_mod.CLOCK()
        elif self._t is not None:
            self.pauses.append((self._t, pump_mod.CLOCK(), info["generation"]))


class CompileClock:
    """Backend compiles reported by JAX's monitoring events (as
    chip_smoke.CompileClock): seconds and count, split at ``mark``."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1


@dataclasses.dataclass
class Context:
    """What a metric reader may read. ``name`` is the metric asked for."""

    name: str
    cell: Cell
    peaks: dict
    setup_s: float
    compile_s: float
    window: tuple                 # (t0, t1) CLOCK seconds of the window
    reqs: list                    # pump.Req of every offered request
    steps: list                   # pump.Step of the traced slice (trace 1)
    serve: dict                   # ServeMetrics summary of the slice
    trace: object = None          # trace.TraceView (trace 1)

    @property
    def config(self) -> dict:
        return self.cell.config


def device_info(jax, trace_view=None) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
    if trace_view is not None:
        info["busy_s"] = trace_view.busy_s()
        info["window_s"] = trace_view.window_s
    return info


def sample(reqs: list, k: int, seed: int) -> list:
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not reqs:
        return []
    longest = max(range(len(reqs)), key=lambda i: reqs[i].plen)
    rest = [i for i in range(len(reqs)) if i != longest]
    rng = np.random.default_rng([int(seed), 11])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [reqs[longest]] + [reqs[rest[i]] for i in sorted(pick)]


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_proc: float, require_tpu: bool = True,
        out=sys.stdout, peaks: Optional[dict] = None,
        control: str = "") -> dict:
    """``control`` (a dtype name, e.g. 'bfloat16') puts the reference
    computed in that dtype in the program's place for the check: the
    control that a limit must fail. The window still runs as usual."""
    use_compile_cache()
    import jax
    from . import spec, system, traffic as traffic_mod, trace as trace_mod

    devs = jax.devices()
    chips = int(cell.entry["chips"])
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        log(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s)")
        raise SystemExit(3)
    if peaks is None:
        peaks = spec.peaks(devs[0].device_kind, cell.bench_dir)
    clock = CompileClock()
    config, tr = cell.config, cell.traffic
    reference = cell.reference()
    log(f"JAX and the chip up at {pump_mod.CLOCK() - t_proc:.3f} s")
    engine, plan = system.build_engine(config, seed, reference)
    log(f"{plan.describe()}: weights, calibration and packing done at "
        f"{pump_mod.CLOCK() - t_proc:.3f} s")
    pump = pump_mod.Pump(engine, tr["task"], annotate=trace)
    warmed = system.warm_shapes(pump, tr, config, config["vocab_size"])
    n = traffic_mod.request_count(tr, seconds)
    arrivals = traffic_mod.make_arrivals(tr, seed, config["vocab_size"], n)
    log(f"warmed with {warmed} requests at {pump_mod.CLOCK() - t_proc:.3f} s;"
        f" {len(arrivals)} arrivals drawn")

    C = pump_mod.CLOCK
    ramp = float(tr.get("ramp_s", 0.0))
    t_first = C()
    t0 = t_first + ramp
    t1 = t0 + seconds

    def drive(until, start):
        return pump.run_open(arrivals, t_first, until, start)

    # what set-up built lives for the whole run: keep it out of the
    # collector's scans, so a full collection in the window stays short
    gc.collect()
    gc.freeze()
    gcc = GcClock()
    idx = drive(t0, 0)
    engine.metrics.pop_summary()
    setup_s = C() - t_proc
    compile_s, compiles_setup = clock.seconds, clock.count
    steps0 = len(pump.steps)
    view = None
    if trace:
        lead = 0.2 * seconds
        span = min(0.5 * seconds, 5.0)
        idx = drive(t0 + lead, idx)
        tdir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
        steps0 = len(pump.steps)
        engine.metrics.pop_summary()
        jax.profiler.start_trace(str(tdir))
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
            idx = drive(t0 + lead + span, idx)
        serve = engine.metrics.pop_summary()
        steps_slice = pump.steps[steps0:]
        jax.profiler.stop_trace()
        idx = drive(t1, idx)
    else:
        idx = drive(t1, idx)
        serve = engine.metrics.pop_summary()
        steps_slice = pump.steps[steps0:]
    in_window = clock.count - compiles_setup
    if trace:
        view = trace_mod.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
    dev = device_info(jax, view)

    due = [r for r in pump.reqs if t0 <= r.due < t1]
    late = np.array([r.submit_t - r.due for r in due
                     if r.submit_t is not None])
    if late.size:
        log(f"generator lateness over {late.size} requests: p50 "
            f"{np.percentile(late, 50) * 1e3:.3f} ms, p95 "
            f"{np.percentile(late, 95) * 1e3:.3f} ms, max "
            f"{late.max() * 1e3:.3f} ms")
    w = np.asarray(pump_mod.waits(pump.reqs, (t0, t1)))
    if w.size:
        log(f"waits from the due time over {w.size} requests: p50 "
            f"{np.percentile(w, 50) * 1e3:.3f} ms, p95 "
            f"{np.percentile(w, 95) * 1e3:.3f} ms, p99 "
            f"{np.percentile(w, 99) * 1e3:.3f} ms, max {w.max() * 1e3:.3f} ms")
    log(f"compiles inside the window: {in_window}")
    win_steps = [st for st in pump.steps if t0 <= st.t0 < t1]
    if win_steps:
        longest = max(st.t1 - st.t0 for st in win_steps)
        log(f"{len(win_steps)} engine steps in the window, longest "
            f"{longest * 1e3:.3f} ms")
    gcs = [b - a for a, b, _ in gcc.pauses if t0 <= a < t1]
    log(f"{len(gcs)} collector pauses in the window, longest "
        f"{max(gcs, default=0.0) * 1e3:.3f} ms")
    gc.callbacks.remove(gcc._on)
    log(f"set-up {setup_s:.3f} s, of which backend compile {compile_s:.3f} s "
        f"({compiles_setup} programs)")

    ctx = Context(name="", cell=cell, peaks=peaks, setup_s=setup_s,
                  compile_s=compile_s, window=(t0, t1), reqs=list(pump.reqs),
                  steps=steps_slice, serve=serve, trace=view)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        ctx.name = m["name"]
        val = cell.reader(m["name"])(ctx)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    failed = sum(1 for r in due if r.failed)

    # ---- correctness: answers due in the window, a minute's grace
    pump.drain(C() + DRAIN_S)
    unanswered = sum(1 for r in due if not r.done)
    finished = [r for r in due if r.done and not r.failed]
    chosen = sample(finished, int(config["check"]["sample"]), seed)
    del pump, engine, plan
    ctx.reqs = None
    gc.collect()
    t_check = C()
    numbers = reference.check(config, seed, chosen, control=control)
    log(f"reference check of {len(chosen)} answers took "
        f"{C() - t_check:.3f} s")
    limits = dict(config["check"]["limits"])
    checks = {"unanswered": {"value": unanswered, "limit": 0}}
    for name in reference.COMPARED:
        checks[name] = {"value": numbers[name], "limit": limits[name]}
    correct = bool(chosen) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    for name, val in numbers.items():
        if name not in checks:
            log(f"(not compared) {name} {val}")
    result = {"correct": correct, "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": dev}
    if trace and view is not None:
        result["breakdown"] = {"device_ops": view.top_ops(10),
                               "idle_gaps": view.idle_gaps(10)}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), file=out, flush=True)
    return result
