"""Kernels, prefill attention: kv tiles the pinned-tile core visits over
the tiles a loop over every tile would visit, in the main cohort's
prefill launches of the window, in % (program counters ``kv_tiles_live``
and ``kv_tiles_total``, window deltas). A program without the counters
reads nothing.

The harness snapshots a fixed list of counters at the window's edges; a
counter outside it is read from the engine's per-step gauges
(``kv_tiles_live_step``, ``kv_tiles_total_step``) over the window's
steps. Each of the harness's steps is one ``eng.step()``, so the run's
last ``len(run.steps)`` engine steps are its steps, in order."""
from engtrace import engine


def _window_deltas(run):
    c0, c1 = run.c0 or {}, run.c1 or {}
    if "kv_tiles_total" in c0 and "kv_tiles_total" in c1:
        return (c1["kv_tiles_live"] - c0["kv_tiles_live"],
                c1["kv_tiles_total"] - c0["kv_tiles_total"])
    eng = engine(run)
    if eng is None:
        return None
    series = eng.telemetry.gauge_series
    if "kv_tiles_total_step" not in series:
        return None
    first = eng.step_count - len(run.steps)
    by_step = {first + i: s for i, s in enumerate(run.steps)}
    window = {id(s) for s in run.window_steps}
    sums = []
    for name in ("kv_tiles_live_step", "kv_tiles_total_step"):
        n = 0
        for step, v in series[name]:
            s = by_step.get(step)
            if s is not None and not s.prefilled:
                return None      # the steps do not line up
            if s is not None and id(s) in window:
                n += v
        sums.append(n)
    return sums


def read(run):
    d = _window_deltas(run)
    if d is None or not d[1]:
        return None
    return 100.0 * d[0] / d[1]
