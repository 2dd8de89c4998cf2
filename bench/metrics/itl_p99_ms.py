"""99th percentile (nearest rank) of the gaps between consecutive token
emissions of the requests due in the window, in ms (host clock)."""
from readers import gaps, nearest_rank


def read(run):
    v = nearest_rank(gaps(run), 0.99)
    return None if v is None else v * 1e3
