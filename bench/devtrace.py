"""Reduce a JAX profiler trace to device busy time, idle share and the
device time inside each of the harness's own host spans.

The harness wraps the traced stretch in a ``bench.window`` span and every
engine step in a ``bench.step`` span (``jax.profiler.TraceAnnotation``),
so the host's spans and the device's operations share the profiler's
clock. Device operations are the events of one line of each device plane
(on a TPU: planes ``/device:TPU:<n>``, line ``XLA Ops``). The selector is
a parameter so that the arithmetic can be checked on a trace recorded on
the CPU, whose executor threads stand in for the device.
"""
from __future__ import annotations

import glob
import os
from typing import Callable, Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

TPU = {"plane_prefix": "/device:TPU:", "line": "XLA Ops"}


def load(trace_dir: str):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Interval], t0: float, t1: float) -> float:
    """Length of [t0, t1) covered by the merged intervals."""
    return sum(max(0.0, min(e, t1) - max(s, t0)) for s, e in merged)


def gaps(merged: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    """Uncovered stretches of [t0, t1)."""
    out, t = [], t0
    for s, e in merged:
        if e <= t0 or s >= t1:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < t1:
        out.append((t, t1))
    return out


def device_events(pd, plane_prefix: str, line: str,
                  keep: Callable[[str], bool] = lambda n: True):
    """{plane name: [(start_ns, end_ns, op name), ...]}"""
    out: Dict[str, list] = {}
    for plane in pd.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for ln in plane.lines:
            if not ln.name.startswith(line):
                continue
            for e in ln.events:
                if e.duration_ns > 0 and keep(e.name):
                    out.setdefault(plane.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name))
    return out


def host_spans(pd, name: str) -> List[Interval]:
    """Every host event called ``name``, in time order."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name == name:
                    out.append((e.start_ns, e.start_ns + e.duration_ns))
    return sorted(out)


def host_events(pd) -> List[Tuple[float, float, str]]:
    """Events of the host's Python and main threads."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                if ln.name.startswith(("python", "main")):
                    out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in ln.events if e.duration_ns > 0]
    return out


def op_name(name: str) -> str:
    """A device op's event name is its HLO text on a TPU; keep the
    instruction's name (``%while.169``)."""
    return name.split(" = ", 1)[0]


def reduce(pd, *, plane_prefix: str = TPU["plane_prefix"],
           line: str = TPU["line"], keep=lambda n: True,
           window: str = "bench.window", step: str = "bench.step",
           top: int = 10) -> dict:
    """Busy and idle seconds of the traced window (averaged over device
    planes), the device-busy seconds inside each ``step`` span, and the
    breakdown: the operations that took most device time and the longest
    idle gaps, each named by the innermost host event running at its
    middle."""
    win = host_spans(pd, window)
    if not win:
        raise ValueError(f"the trace holds no {window!r} span")
    t0, t1 = win[0]
    planes = device_events(pd, plane_prefix, line, keep)
    if not planes:
        raise ValueError(f"the trace holds no device events on planes "
                         f"{plane_prefix!r}, line {line!r}")
    merged = {p: union([(s, e) for s, e, _ in ev]) for p, ev in planes.items()}
    busy = sum(covered(m, t0, t1) for m in merged.values()) / len(merged)
    steps = [s for s in host_spans(pd, step) if t0 <= s[0] and s[1] <= t1]
    step_busy = [sum(covered(m, s, e) for m in merged.values()) / len(merged)
                 for s, e in steps]
    ops: Dict[str, float] = {}
    for ev in planes.values():
        for s, e, n in ev:
            d = min(e, t1) - max(s, t0)
            if d > 0:
                n = op_name(n)
                ops[n] = ops.get(n, 0.0) + d
    first = sorted(planes)[0]
    host = host_events(pd)
    idle = []
    for s, e in gaps(merged[first], t0, t1):
        mid = (s + e) / 2
        inner = [h for h in host if h[0] <= mid < h[1]
                 and h[2] not in (window,)]
        label = min(inner, key=lambda h: h[1] - h[0])[2] if inner else "none"
        idle.append((label, (e - s) * 1e-9))
    idle.sort(key=lambda t: -t[1])
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy * 1e-9,
        "step_spans_s": [((s - t0) * 1e-9, (e - t0) * 1e-9) for s, e in steps],
        "step_busy_s": [b * 1e-9 for b in step_busy],
        "device_ops": [[n, d * 1e-9] for n, d in
                       sorted(ops.items(), key=lambda t: -t[1])[:top]],
        "idle_gaps": [[n, d] for n, d in idle[:top]],
    }


class Tracer:
    """Profile a stretch of steady traffic: the profiler starts at the
    first step boundary at or after ``start`` and stops at the first one
    at or after ``start + length``. While it runs, the stretch is one
    ``bench.window`` span, every engine step a ``bench.step`` span, and the
    driver's submissions and sleeps ``bench.submit`` and ``bench.idle``.
    Python function tracing stays off: it would slow the host it
    measures."""

    def __init__(self, directory: str, start: float, length: float):
        self.dir, self.on, self.off = directory, start, start + length
        self.active = self.done = False
        self._win = None

    def poll(self, now: float) -> None:
        import jax
        if not self.active and not self.done and now >= self.on:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._win = jax.profiler.TraceAnnotation("bench.window")
            self._win.__enter__()
            self.active = True
        elif self.active and now >= self.off:
            self.stop()

    def step(self):
        return self.span("bench.step")

    def span(self, name: str):
        """A host span while the profiler runs, else None."""
        import jax
        return jax.profiler.TraceAnnotation(name) if self.active else None

    def stop(self) -> None:
        import jax
        if self.active:
            self._win.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active, self.done = False, True
