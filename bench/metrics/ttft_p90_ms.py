"""90th percentile (nearest rank) of time to first token, in ms, over every
request due in the window, each timed from its due time (host clock)."""
from readers import nearest_rank, ttfts


def read(run):
    v = nearest_rank(ttfts(run), 0.90)
    return None if v is None else v * 1e3
