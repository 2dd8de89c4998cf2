"""What the metric readers share: a run's records and the arithmetic on
them. Each metric is its own file in ``bench/metrics/`` with
``read(run) -> float | None``; None means the run holds nothing for that
metric to read, and the metric is left out of the result line."""
from __future__ import annotations

import math
from typing import List, Optional, Sequence


def nearest_rank(xs: Sequence[float], q: float) -> Optional[float]:
    """The q-quantile by nearest rank: the smallest value with at least a
    share ``q`` of the sample at or below it."""
    if not xs:
        return None
    s = sorted(xs)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def ttfts(run) -> List[float]:
    """Seconds from due time to first token, for every request due in the
    window. One that never produced a token counts as waiting until the
    run ended, which is less than it would have waited."""
    return [(r.first if r.first is not None else run.end) - r.due
            for r in run.window_recs]


def gaps(run) -> List[float]:
    """Gaps between consecutive token emissions of window requests."""
    out = []
    for r in run.window_recs:
        t = [e[0] for e in r.emits]
        out += [b - a for a, b in zip(t, t[1:])]
    return out


def window_tokens(run) -> int:
    return sum(n for r in run.recs for t, n in r.emits
               if run.start < t <= run.window_end)


def traced(run):
    """(traced steps, reduced trace) or (None, None) without a trace."""
    if run.trace is None:
        return None, None
    steps = [s for s in run.steps if s.traced]
    busy = run.trace["step_busy_s"]
    if len(steps) != len(busy):
        raise ValueError(f"{len(steps)} traced steps but {len(busy)} "
                         "bench.step spans in the trace")
    return steps, busy


def decode_step_s(run) -> Optional[float]:
    """Mean device time of steps that only decoded (no prefill launch)."""
    steps, busy = traced(run)
    if steps is None:
        return None
    xs = [b for s, b in zip(steps, busy) if s.decoded and not s.prefilled]
    return sum(xs) / len(xs) if xs else None


def prefill_s_per_token(run) -> Optional[float]:
    """Device time of the prefill launches per prompt token that requests
    needed computed: a step's device time less one decode step's where
    it also decoded. Padding and cache hits are not in the token count."""
    steps, busy = traced(run)
    if steps is None:
        return None
    dec = decode_step_s(run)
    if dec is None:
        return None
    t = sum(b - (dec if s.decoded else 0.0)
            for s, b in zip(steps, busy) if s.prefilled)
    n = sum(s.prefilled for s in steps)
    return t / n if n else None


def idle_share(run) -> Optional[float]:
    if run.trace is None:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]


def step_mfu(run) -> Optional[float]:
    """Useful operations of the traced steps over their summed wall time
    at the chip's peak."""
    steps, _ = traced(run)
    if not steps or run.peak is None:
        return None
    wall = sum(s.t1 - s.t0 for s in steps)
    return sum(s.flops for s in steps) / (wall * run.peak["bf16_flops"])
