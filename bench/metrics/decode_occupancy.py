"""Scheduler: decode rows per step over the slot count, in %, averaged
over the window's steps that decoded (program counter ``decoded``)."""


def read(run):
    xs = [s.decoded for s in run.window_steps if s.decoded]
    return 100.0 * sum(xs) / (len(xs) * run.n_slots) if xs else None
