"""Device: share of the traced stretch in which no operation ran on the
chip, in % (device trace)."""
from readers import idle_share


def read(run):
    v = idle_share(run)
    return None if v is None else 100.0 * v
