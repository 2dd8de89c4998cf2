"""Model step, decode: device time of the ``decode_*`` programs (leaf ops
of their modules) per decode launch in the traced stretch, in ms (device
trace, by the engine's program names)."""
from engtrace import per_launch


def read(run):
    v = per_launch(run, "decode")
    return None if v is None else v * 1e3
