"""Drive the engine from the traffic's wall-clock schedule and record what
every request saw.

The loop submits each request when it is due (``PagedEngine.add_request``)
and otherwise runs ``PagedEngine.step()``, which ends in a host read of
the step's tokens. A request's tokens are stamped with the time the step
that produced them returned: that is when a server built on the engine
could send them. Every time is on ``time.perf_counter`` and relative to
the traffic clock's origin, and latency counts from the request's due
time, so a step that delays a submission is counted against it. The
generator's lateness (submission - due) is recorded beside.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

import flops as FL


def _span(tracer, name):
    s = tracer.span(name) if tracer is not None else None
    return s if s is not None else contextlib.nullcontext()


@dataclasses.dataclass
class Rec:
    rid: int
    item: object                     # traffic.Item
    submit: float
    admit: Optional[float] = None    # start of the step that admitted it
    hit: int = 0                     # prompt tokens served from the cache
    emits: List[tuple] = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    ok: Optional[bool] = None
    out: Optional[np.ndarray] = None

    @property
    def due(self) -> float:
        return self.item.due

    @property
    def first(self) -> Optional[float]:
        return self.emits[0][0] if self.emits else None

    @property
    def n_out(self) -> int:
        return sum(n for _, n in self.emits)


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decoded: int        # decode rows this step
    prefilled: int      # prompt tokens computed (no hits, no padding)
    flops: int          # useful operations (flops.py)
    traced: bool = False


COUNTERS = ("prefill_tokens", "hit_tokens", "bucket_groups", "pad_tokens",
            "decoded", "prefix_hits", "admitted", "finished", "failed")


class Driver:
    def __init__(self, eng, traffic, m: dict, *, preroll: float,
                 seconds: float, drain: float, tracer=None):
        from repro.serve.scheduler import FINISHED, TERMINAL_STATES
        self.FINISHED, self.TERMINAL = FINISHED, TERMINAL_STATES
        self.eng, self.traffic, self.m = eng, traffic, m
        self.ws, self.we = preroll, preroll + seconds
        self.drain = drain
        self.tracer = tracer
        self.recs: Dict[int, Rec] = {}
        self.live: Dict[int, Rec] = {}
        self.steps: List[Step] = []
        self.start: Optional[float] = None      # the window's first tick
        self.window_end: Optional[float] = None
        self.closed_at: Optional[float] = None  # the drain counts from here
        self.c0 = self.c1 = None
        self.lateness: List[float] = []

    def counters(self) -> dict:
        c = self.eng.counters
        return {k: int(c[k]) for k in COUNTERS}

    def window_recs(self) -> List[Rec]:
        return [r for r in self.recs.values() if self.ws <= r.due < self.we]

    def _submit(self, now: float) -> None:
        for it in self.traffic.pop_due(now, self.eng.sched.n_queued):
            rid = self.eng.add_request(it.prompt, it.max_new)
            self.recs[rid] = self.live[rid] = Rec(rid, it, now)
            self.lateness.append(now - it.due)

    def _step(self, clock) -> None:
        eng, m = self.eng, self.m
        c0 = eng.counters
        pre0, dec0 = int(c0["prefill_tokens"]), int(c0["decoded"])
        tr = self.tracer.step() if self.tracer else None
        t0 = clock()
        if tr is not None:
            with tr:
                eng.step()
        else:
            eng.step()
        t1 = clock()
        ops = 0
        ps = eng.psv.page_size
        for rid, rec in list(self.live.items()):
            r = eng.request(rid)
            n = len(r.out)
            seen = rec.n_out
            if rec.admit is None and r.admitted_step >= 0:
                rec.admit = t0
                rec.hit = r.n_shared * ps
                if n:
                    ops += FL.prefill_flops(m, rec.hit, len(r.prompt))
                    seen_dec = 1
                else:
                    seen_dec = 0
            else:
                seen_dec = seen
            lp = len(r.prompt)
            for k in range(max(seen_dec, 1), n):   # decode rows fed k-1
                ops += FL.decode_flops(m, lp + k - 1)
            if n > seen:
                rec.emits.append((t1, n - seen))
            if r.status in self.TERMINAL:
                rec.done, rec.ok = t1, r.status == self.FINISHED
                rec.out = np.asarray(r.out, np.int32)
                del self.live[rid]
                self.traffic.finished(rec.item, rec.out, t1)
        c1 = eng.counters
        self.steps.append(Step(t0, t1, int(c1["decoded"]) - dec0,
                               int(c1["prefill_tokens"]) - pre0, ops,
                               tr is not None))

    def run(self, clock, *, finished_after: int = 0) -> None:
        """Pre-roll, window, then drain. Open traffic: arrivals go on until
        every request due in the window has ended, or ``drain`` seconds
        after the window's end (the ones still open then count as
        failed). A backlog takes no new work after the window and steps
        until ``finished_after`` requests have finished since the
        window's start (capped the same way), so that the check has
        finished requests to read. With a tracer, the drain starts when
        the traced stretch has ended, and takes no new work either:
        stopping the profiler stalls the host for tens of seconds, and
        the arrivals it held back would only flood the engine while the
        window's last requests finish, with nothing left to measure."""
        eng = self.eng
        backlog = self.traffic.kind == "backlog"
        while True:
            now = clock()
            if self.start is None and now >= self.ws:
                self.c0, self.start = self.counters(), now
            if self.window_end is None and now >= self.we:
                self.window_end, self.c1 = now, self.counters()
            if self.tracer:
                self.tracer.poll(now)
            # The profiler traces a stretch after the window, while the
            # traffic goes on: starting and stopping it stalls the host for
            # seconds, which must not land on the window's requests.
            closed = self.window_end is not None and (
                self.tracer is None or self.tracer.done)
            if closed and self.closed_at is None:
                self.closed_at = self.we if self.tracer is None else clock()
            if closed and now >= self.closed_at + self.drain:
                break
            # Submit before asking whether the window's requests are all
            # done: a step that ran past the window's end (a compile) must
            # not leave the requests due meanwhile unsubmitted and uncounted.
            if not (closed and (backlog or self.tracer is not None)):
                with _span(self.tracer, "bench.submit"):
                    self._submit(now)
            if closed:
                if backlog:
                    done = sum(r.done is not None and r.done > self.start
                               for r in self.recs.values())
                    if done >= finished_after or not eng.sched.n_running:
                        break
                elif all(r.done is not None for r in self.window_recs()):
                    break
            if eng.sched.n_queued or eng.sched.n_running:
                self._step(clock)
                continue
            nxt = self.traffic.next_due()
            bound = self.ws if self.start is None else self.we
            target = min(x for x in (nxt, bound,
                                     (self.closed_at or self.we) + self.drain)
                         if x is not None)
            if target <= now and closed and nxt is None:
                break
            with _span(self.tracer, "bench.idle"):
                time.sleep(max(0.0, min(target - now, 0.05)))
        if self.tracer:
            self.tracer.stop()

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps
                if s.t0 >= self.start and s.t1 <= self.window_end]
