"""Model step, decode: mean device time of an engine step that ran only
the decode program, in ms (device trace)."""
from readers import decode_step_s


def read(run):
    v = decode_step_s(run)
    return None if v is None else v * 1e3
