"""Reduce a JAX profiler trace to busy time, kernel time and idle gaps.

The harness wraps the traced slice in a host span named ``WINDOW`` and
every call into the engine in spans of its own (``engine_step``,
``submit``, ``generator``). The device planes hold one event per
operation that ran. From those this module computes:

* busy: the union of the device operations' intervals inside the window,
  averaged over the devices that ran anything;
* the device time of the operations a kernel's instruction name marks
  (a Pallas kernel's HLO instruction carries the kernel's name);
* the longest idle gaps, each labelled by the host span that covers it.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

WINDOW = "bench_window"
HOST_SPANS = ("engine_step", "submit", "generator")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Op:
    name: str        # the HLO instruction's name, numeric suffix dropped
    t0: int          # ns
    t1: int
    self_ns: int = 0  # duration less that of the operations nested in it


def op_name(event_name: str) -> str:
    """``%int4_matmul_fused.3 = f32[...] custom-call(...)`` ->
    ``int4_matmul_fused``: the instruction's name without its number."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head) or event_name


@dataclasses.dataclass
class TraceView:
    window: tuple            # (t0, t1) ns, host clock of the trace
    ops: dict                # device plane name -> [Op], clipped to window
    host: list               # [(name, t0, t1)] harness spans in window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def devices(self) -> list:
        return [d for d, ops in self.ops.items() if ops]

    def busy_intervals(self, device: str) -> list:
        return _union([(o.t0, o.t1) for o in self.ops[device]])

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran operations."""
        devs = self.devices()
        if not devs:
            return 0.0
        tot = sum(sum(b - a for a, b in self.busy_intervals(d)) for d in devs)
        return tot / len(devs) * 1e-9

    def kernel_s(self, names) -> float:
        """Device seconds of the operations named ``names`` (a Pallas
        kernel's instruction carries the kernel's name), over devices."""
        names = set(names)
        return sum(o.t1 - o.t0 for ops in self.ops.values() for o in ops
                   if o.name in names) * 1e-9

    def top_ops(self, k: int = 10) -> list:
        """The operations with the most self time (a loop's own time
        excludes the operations it runs)."""
        tot: dict = {}
        for ops in self.ops.values():
            for o in ops:
                tot[o.name] = tot.get(o.name, 0) + o.self_ns
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, ns * 1e-9] for n, ns in top]

    def idle_gaps(self, k: int = 10) -> list:
        """The ``k`` longest idle gaps of the first busy device, labelled by
        the innermost harness span over each gap's midpoint."""
        devs = self.devices()
        if not devs:
            return []
        busy = self.busy_intervals(devs[0])
        t0, t1 = self.window
        gaps, cur = [], t0
        for a, b in busy:
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if cur < t1:
            gaps.append((cur, t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            mid = (a + b) // 2
            cover = [(n, s, e) for n, s, e in self.host if s <= mid <= e]
            label = (min(cover, key=lambda c: c[2] - c[1])[0] if cover
                     else "outside harness spans")
            out.append([label, (b - a) * 1e-9])
        return out


def _union(iv: list) -> list:
    out: list = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(ops: list) -> None:
    """Fill ``self_ns``: events of one line nest (a while loop holds its
    body's operations); each event's self time excludes its children."""
    ops.sort(key=lambda o: (o.t0, -o.t1))
    stack: list = []
    for o in ops:
        o.self_ns = o.t1 - o.t0
        while stack and stack[-1].t1 <= o.t0:
            stack.pop()
        if stack and o.t1 <= stack[-1].t1:
            stack[-1].self_ns -= o.t1 - o.t0
        stack.append(o)


def newest_xspace(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: Path) -> TraceView:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(newest_xspace(trace_dir)))
    win, host = None, []
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    win = (ev.start_ns, ev.end_ns)
                elif ev.name in HOST_SPANS:
                    host.append((ev.name, ev.start_ns, ev.end_ns))
    if win is None:
        raise RuntimeError(f"trace has no host span {WINDOW!r}")
    t0, t1 = win
    ops: dict = {}
    for plane in device_planes:
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
        evs = []
        for line in lines:
            line_ops = []
            for ev in line.events:
                a, b = max(ev.start_ns, t0), min(ev.end_ns, t1)
                if b > a:
                    line_ops.append(Op(op_name(ev.name), int(a), int(b)))
            _self_times(line_ops)
            evs += line_ops
        ops[plane.name] = evs
    host = [(n, max(a, t0), min(b, t1)) for n, a, b in host if b > t0 and a < t1]
    return TraceView(window=(t0, t1), ops=ops, host=host)
