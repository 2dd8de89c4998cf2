"""Device, whole step: useful model operations of the traced engine steps
(bench/flops.py) over their summed wall time times the chip's bf16 peak,
in % (host clock around each step, which ends in a host read)."""
from readers import step_mfu


def read(run):
    v = step_mfu(run)
    return None if v is None else 100.0 * v
