"""Host: mean device idle from the last device op of one ``engine.step``
to the first device op of the next, over consecutive pairs of steps that
only decoded, in ms (device trace against the engine's own step spans)."""
from engtrace import of


def read(run):
    r = of(run)
    if r is None or not r["host_gaps_s"]:
        return None
    return 1e3 * sum(r["host_gaps_s"]) / len(r["host_gaps_s"])
