"""Scheduler: 90th percentile (nearest rank) of the wait from a window
request's due time to the start of the engine step that admitted it, in
ms (host clock). A request never admitted waits until the run's end."""
from readers import nearest_rank


def read(run):
    w = [(r.admit if r.admit is not None else run.end) - r.due
         for r in run.window_recs]
    v = nearest_rank(w, 0.90)
    return None if v is None else v * 1e3
