"""Process start to the window's start: imports, weights, engine, compile
or cache loads, warm-up and pre-roll (host clock)."""


def read(run):
    return run.setup_s
