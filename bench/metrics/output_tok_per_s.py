"""Output tokens emitted inside the window over the window's length
(host clock; the window ends at the end of the step running at its
nominal end)."""
from readers import window_tokens


def read(run):
    return window_tokens(run) / (run.window_end - run.start)
