"""Prefix cache: prompt tokens served from cached pages over all prompt
tokens admitted in the window, in % (program counters ``hit_tokens`` and
``prefill_tokens``, window deltas)."""


def read(run):
    hit = run.c1["hit_tokens"] - run.c0["hit_tokens"]
    pre = run.c1["prefill_tokens"] - run.c0["prefill_tokens"]
    return 100.0 * hit / (hit + pre) if hit + pre else None
