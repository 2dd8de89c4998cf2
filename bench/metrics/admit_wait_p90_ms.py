"""Scheduler: 90th percentile (nearest rank) over window requests of the
engine's own admission stamp less its submission stamp (the ``ADMITTED``
and ``SUBMITTED`` events of its request spans, both on the host clock),
in ms. A request never admitted waits until the run ends."""
from engtrace import engine
from readers import nearest_rank


def read(run):
    eng = engine(run)
    if eng is None:
        return None
    w = []
    for r in run.window_recs:
        span = eng.telemetry.span(r.rid)
        if span is None:
            return None
        adm = [e.wall for e in span.events if e.state == "admitted"]
        sub = span.events[0].wall
        w.append(adm[0] - sub if adm else run.end - r.submit)
    v = nearest_rank(w, 0.90)
    return None if v is None else v * 1e3
