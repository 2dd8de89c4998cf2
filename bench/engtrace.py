"""Reduce a profiler trace by the engine's own names: device time by
program and model scope, and the device's idle time by host phase.

    python3 bench/engtrace.py <trace dir>    # a trace kept from a run

The program under test marks three things in a ``jax.profiler`` trace:

- every compiled engine program is named after its cache key
  (``decode_main_12``, ``prefill_bucket_main_512x8``), so its module is
  ``jit_<name>``: on a TPU the device plane's ``XLA Modules`` line holds
  one event per launch, and on the CPU each op carries an ``hlo_module``
  stat;
- the model's ops sit under named scopes (``layer_scan``, ``attn``,
  ``attn_core``, ``mlp``, ``head``, ``sample``, ``kv_write``,
  ``kv_gather``), which reach the trace as the op's ``op_name`` path
  (``jit(decode_main_12)/layer_scan/while/body/.../attn/attn_core/...``);
  a TPU profile keeps it in the ``tf_op`` stat of the op's event
  metadata, which ``jax.profiler.ProfileData`` does not expose, so only a
  trace read with ``load`` has scopes;
- the host phases of every engine step are spans (``engine.step``,
  ``engine.admit``, ``engine.prefill``, ``engine.decode``,
  ``engine.readback``, ``engine.bookkeep``), beside the harness's own
  ``bench.*`` spans.

Only leaf ops count as device time: an op that holds another event of its
line (a ``%while`` holds its body) would count the body twice. A leaf op
is charged to the innermost known scope on its ``op_name`` path, or to
``unattributed``. Each idle stretch of the device is split across the
innermost ``engine.*`` or ``bench.*`` span running over each part of it.

A program that does not name its programs (every module ``jit_f``) gives
the readers nothing to read.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from types import SimpleNamespace
from typing import Dict, List, Optional, Tuple

import devtrace as DT

#: Named scopes of the model and the engine's programs. An op is charged
#: to the last of these on its ``op_name`` path (``kv_write`` inside
#: ``attn`` is ``kv_write``).
SCOPES = ("layer_scan", "attn", "attn_core", "mlp", "head", "sample",
          "kv_write", "kv_gather")
UNATTRIBUTED = "unattributed"
#: Program families, by the first word of the program's name.
FAMILIES = ("decode", "prefill")
#: The CPU stand-in for a device plane (tests): the executor's thread.
CPU = {"plane_prefix": "/host:CPU", "line": "tf_XLAPjRtCpuClient",
       "keep": lambda n: not n.startswith(("ThreadpoolListener", "end:"))}

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def program_of_module(name: str) -> str:
    """``jit_decode_main_12(8812...)`` -> ``decode_main_12``."""
    name = name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def family(program: str) -> str:
    """``decode``, ``prefill`` or ``other``."""
    first = program.split("_", 1)[0]
    return first if first in FAMILIES else "other"


def op_path(name: str, stats: Dict[str, object]) -> Tuple[str, str]:
    """(op_name metadata path, where it was found) of one device op: the
    ``tf_op`` stat, an ``op_name="..."`` in the ``long_name`` stat or in
    the event's name, else ('', '')."""
    v = stats.get("tf_op")
    if isinstance(v, str) and v:
        return v.rsplit(":", 1)[0], "tf_op"     # "<op_name>:<op type>"
    for where, text in (("long_name", stats.get("long_name")),
                        ("name", name)):
        if isinstance(text, str):
            m = _OP_NAME.search(text)
            if m:
                return m.group(1), where
    return "", ""


def scope_of(path: str) -> str:
    """The innermost known scope on an ``op_name`` path."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return UNATTRIBUTED


def leaves(events: List[tuple]) -> List[tuple]:
    """The events of one line that hold no other event of it."""
    ev = sorted(events, key=lambda e: (e[0], -e[1]))
    return [e for i, e in enumerate(ev)
            if i + 1 == len(ev) or ev[i + 1][0] >= e[1]]


def _device_lines(pd, plane_prefix, line, keep):
    """{plane: (ops [(start, end, name, stats)], modules [(start, end,
    program)])}; modules is empty where the plane has no module line."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        ops, mods = [], []
        for ln in plane.lines:
            if ln.name.startswith(line):
                ops += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                         dict(e.stats)) for e in ln.events
                        if e.duration_ns > 0 and keep(e.name)]
            elif ln.name == "XLA Modules":
                mods += [(e.start_ns, e.start_ns + e.duration_ns,
                          program_of_module(e.name)) for e in ln.events
                         if e.duration_ns > 0]
        if ops:
            out[plane.name] = (ops, sorted(mods))
    return out


def _host_spans(pd) -> List[Tuple[float, float, str]]:
    """Every ``engine.*`` and ``bench.*`` span of the host."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in ln.events
                        if e.name.startswith(("engine.", "bench."))]
    return sorted(out, key=lambda s: (s[0], -s[1]))


def innermost(spans, t0: float, t1: float) -> List[Tuple[float, float, str]]:
    """[t0, t1) cut into pieces, each labelled with the innermost span
    running over it (spans nest: they come from one thread); a piece
    under no span is labelled ``none``."""
    out: List[list] = []

    def emit(a, b, name):
        a, b = max(a, t0), min(b, t1)
        if b > a:
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1][1] = b
            else:
                out.append([a, b, name])

    stack: List[tuple] = []
    t = t0
    for s, e, n in spans:
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            emit(t, top[1], top[2])
            t = max(t, top[1])
        emit(t, s, stack[-1][2] if stack else "none")
        t = max(t, s)
        stack.append((s, e, n))
    while stack:
        top = stack.pop()
        emit(t, top[1], top[2])
        t = max(t, top[1])
    emit(t, t1, "none")
    return [tuple(p) for p in out]


def split_idle(idle, pieces) -> Dict[str, float]:
    """Length of the idle stretches under each label of ``pieces``."""
    starts = [p[0] for p in pieces]
    out: Dict[str, float] = {}
    for a, b in idle:
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(pieces) and pieces[i][0] < b:
            s, e, n = pieces[i]
            d = min(b, e) - max(a, s)
            if d > 0:
                out[n] = out.get(n, 0.0) + d
            i += 1
    return out


def _steps(spans, t0, t1):
    """(start, end, decode-only) of every ``engine.step`` in [t0, t1]."""
    steps = [s for s in spans if s[2] == "engine.step"
             and t0 <= s[0] and s[1] <= t1]
    inner = [s for s in spans if s[2] in ("engine.decode", "engine.prefill")]
    out = []
    for s, e, _ in steps:
        kinds = {n for a, b, n in inner if s <= a and b <= e}
        out.append((s, e, kinds == {"engine.decode"}))
    return out


def host_gaps(ops, merged, steps) -> List[float]:
    """Device idle from the last device op of one decode-only step to the
    first of the next, for every consecutive pair of such steps (an op
    belongs to the step whose span it starts in)."""
    ops = sorted(ops)
    starts = [o[0] for o in ops]

    def ops_in(s, e):
        return ops[bisect.bisect_left(starts, s):bisect.bisect_left(starts, e)]

    out = []
    for (s0, e0, d0), (s1, e1, d1) in zip(steps, steps[1:]):
        if not (d0 and d1):
            continue
        a, b = ops_in(s0, e0), ops_in(s1, e1)
        if a and b:
            lo, hi = max(o[1] for o in a), b[0][0]
            out.append(sum(e - s for s, e in DT.gaps(merged, lo, hi)))
    return out


def reduce(pd, *, plane_prefix: str = DT.TPU["plane_prefix"],
           line: str = DT.TPU["line"], keep=lambda n: True,
           window: str = "bench.window") -> Optional[dict]:
    """The trace by the engine's names, in seconds, over the first device
    plane and the ``window`` span; None where the trace holds no window
    or no device ops."""
    win = DT.host_spans(pd, window)
    planes = _device_lines(pd, plane_prefix, line, keep)
    if not win or not planes:
        return None
    t0, t1 = win[0]
    ops, mods = planes[sorted(planes)[0]]
    mod_starts = [m[0] for m in mods]

    def program(s, stats):
        if mods:
            i = bisect.bisect_right(mod_starts, s) - 1
            return mods[i][2] if i >= 0 and s < mods[i][1] else "unknown"
        return program_of_module(str(stats.get("hlo_module", "unknown")))

    by: Dict[Tuple[str, str], float] = {}
    sources: Dict[str, int] = {}
    runs: Dict[str, set] = {}
    for s, e, n, stats in leaves([o for o in ops if t0 <= o[0] < t1]):
        prog = program(s, stats)
        path, where = op_path(n, stats)
        sources[where or "none"] = sources.get(where or "none", 0) + 1
        key = (family(prog), scope_of(path))
        by[key] = by.get(key, 0.0) + (min(e, t1) - s) * 1e-9
        if not mods:
            runs.setdefault(family(prog), set()).add(
                (prog, stats.get("run_id")))
    launches: Dict[str, int] = {}
    if mods:
        for s, _, prog in mods:
            if t0 <= s < t1:
                f = family(prog)
                launches[f] = launches.get(f, 0) + 1
    else:
        launches = {f: len(r) for f, r in runs.items()}
    device_s: Dict[str, float] = {}
    for (f, _), v in by.items():
        device_s[f] = device_s.get(f, 0.0) + v
    spans_dev = [(s, e) for s, e, _, _ in ops]
    merged = DT.union(spans_dev)
    spans = _host_spans(pd)
    idle = DT.gaps(merged, t0, t1)
    return {
        "window_s": (t1 - t0) * 1e-9,
        "programs": sorted({m[2] for m in mods if t0 <= m[0] < t1}),
        "launches": launches,
        "device_s": device_s,
        "scope_s": {f"{f}/{sc}": v for (f, sc), v in sorted(by.items())},
        "unattributed_share": {
            f: by.get((f, UNATTRIBUTED), 0.0) / v
            for f, v in device_s.items() if v > 0},
        "op_name_source": sources,
        "idle_s": sum(e - s for s, e in idle) * 1e-9,
        "idle_by_phase_s": {n: v * 1e-9 for n, v in sorted(
            split_idle(idle, innermost(spans, t0, t1)).items())},
        "host_gaps_s": [g * 1e-9 for g in
                        host_gaps(spans_dev, merged, _steps(spans, t0, t1))],
    }


def _xspace():
    """The ``XSpace`` message class of the profiler's ``.xplane.pb``: the
    fields read here of tsl/profiler/protobuf/xplane.proto (maps in their
    wire form, repeated key/value entries), built with
    ``google.protobuf`` alone."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    T = {"i64": F.TYPE_INT64, "u64": F.TYPE_UINT64, "f64": F.TYPE_DOUBLE,
         "str": F.TYPE_STRING}
    fd = descriptor_pb2.FileDescriptorProto(
        name="engtrace_xplane.proto", package="engtrace", syntax="proto3")
    schema = {
        "XSpace": [("planes", 1, "*XPlane")],
        "XPlane": [("name", 2, "str"), ("lines", 3, "*XLine"),
                   ("event_metadata", 4, "*EM"), ("stat_metadata", 5, "*SM")],
        "EM": [("key", 1, "i64"), ("value", 2, "XEventMetadata")],
        "SM": [("key", 1, "i64"), ("value", 2, "XStatMetadata")],
        "XLine": [("name", 2, "str"), ("timestamp_ns", 3, "i64"),
                  ("events", 4, "*XEvent")],
        "XEvent": [("metadata_id", 1, "i64"), ("offset_ps", 2, "i64"),
                   ("duration_ps", 3, "i64"), ("stats", 4, "*XStat")],
        "XStat": [("metadata_id", 1, "i64"), ("double_value", 2, "f64"),
                  ("uint64_value", 3, "u64"), ("int64_value", 4, "i64"),
                  ("str_value", 5, "str"), ("ref_value", 7, "u64")],
        "XEventMetadata": [("name", 2, "str"), ("stats", 5, "*XStat")],
        "XStatMetadata": [("name", 2, "str")],
    }
    for msg, fields in schema.items():
        m = fd.message_type.add(name=msg)
        for name, num, typ in fields:
            rep = typ.startswith("*")
            typ = typ.lstrip("*")
            f = m.field.add(name=name, number=num, label=(
                F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL))
            if typ in T:
                f.type = T[typ]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".engtrace.{typ}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("engtrace.XSpace"))


class _Event:
    __slots__ = ("name", "start_ns", "duration_ns", "stats")

    def __init__(self, name, start_ns, duration_ns, stats):
        self.name, self.start_ns = name, start_ns
        self.duration_ns, self.stats = duration_ns, stats


class _Line:
    def __init__(self, line, names, stats):
        self.name, self._line = line.name, line
        self._names, self._stats = names, stats

    @property
    def events(self):
        t0 = self._line.timestamp_ns
        for e in self._line.events:
            yield _Event(self._names.get(e.metadata_id, ""),
                         t0 + e.offset_ps * 1e-3, e.duration_ps * 1e-3,
                         self._stats(e))


class _Plane:
    def __init__(self, plane):
        self.name = plane.name
        sm = {x.key: x.value.name for x in plane.stat_metadata}

        def value(st):
            if st.str_value:
                return st.str_value
            if st.ref_value:
                return sm.get(st.ref_value, "")
            return st.int64_value or st.uint64_value or st.double_value

        meta = {x.key: [(sm.get(st.metadata_id, ""), value(st))
                        for st in x.value.stats]
                for x in plane.event_metadata}
        names = {x.key: x.value.name for x in plane.event_metadata}
        self.lines = [_Line(ln, names, lambda e: meta.get(e.metadata_id, [])
                            + [(sm.get(st.metadata_id, ""), value(st))
                               for st in e.stats])
                      for ln in plane.lines]


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``, shaped like
    ``jax.profiler.ProfileData`` (planes, lines, events with ``name``,
    ``start_ns``, ``duration_ns``, ``stats``), with each event's metadata
    stats among its stats. ``ProfileData`` leaves those out, and a TPU
    keeps an op's ``tf_op`` (its ``op_name``) there."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    xs = _xspace()()
    with open(max(paths, key=os.path.getmtime), "rb") as f:
        xs.ParseFromString(f.read())
    return SimpleNamespace(planes=[_Plane(p) for p in xs.planes])


def _harness_local(run, name: str):
    """A local of the harness frame that hands ``run`` to the readers.
    ``bench/run.py`` keeps the loaded profile (``pd``) and the engine
    (``eng``) there but does not put them on ``run``; until it does, the
    readers find them on the stack."""
    f = sys._getframe(1)
    while f is not None:
        loc = f.f_locals
        if loc.get("run") is run and name in loc:
            return loc[name]
        f = f.f_back
    return None


def of(run) -> Optional[dict]:
    """``reduce`` of the run's trace, once per run (kept on ``run``);
    None for a run without a trace."""
    if run.trace is None:
        return None
    if not hasattr(run, "engtrace"):
        pd = _harness_local(run, "pd")
        sel = {} if pd is None or any(
            p.name.startswith(DT.TPU["plane_prefix"]) for p in pd.planes) \
            else CPU
        run.engtrace = None if pd is None else reduce(pd, **sel)
    return run.engtrace


def engine(run):
    """The run's engine, while the harness still holds it."""
    return _harness_local(run, "eng")


def per_launch(run, fam: str) -> Optional[float]:
    """Device seconds of the programs of family ``fam`` per launch of
    one; None where the trace names no such program."""
    r = of(run)
    if r is None or not r["launches"].get(fam):
        return None
    return r["device_s"].get(fam, 0.0) / r["launches"][fam]


def per_prompt_token(run) -> Optional[float]:
    """Device seconds of the prefill programs per prompt token computed
    in the traced steps (the count ``prefill_ms_per_ktok`` uses)."""
    r = of(run)
    if r is None or not r["launches"].get("prefill"):
        return None
    n = sum(s.prefilled for s in run.steps if s.traced)
    return r["device_s"].get("prefill", 0.0) / n if n else None


def main(argv=None) -> int:
    """Print the reduction of a trace directory kept from a run."""
    import argparse
    import json
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("trace_dir")
    ap.add_argument("--window", default="bench.window",
                    help="the host span that bounds the stretch")
    args = ap.parse_args(argv)
    r = reduce(load(args.trace_dir), window=args.window)
    print(json.dumps(r, indent=1))
    return 0 if r is not None else 1


if __name__ == "__main__":
    sys.exit(main())
