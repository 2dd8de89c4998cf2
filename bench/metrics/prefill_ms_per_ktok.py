"""Model step, prefill: device time of the prefill launches in the traced
stretch per 1000 prompt tokens that requests needed computed (device
trace; padding and cache hits are not counted as tokens, so they show as
cost)."""
from readers import prefill_s_per_token


def read(run):
    v = prefill_s_per_token(run)
    return None if v is None else v * 1e6
