"""Model step, prefill: device time of the ``prefill_*`` programs (leaf
ops of their modules) in the traced stretch per 1000 prompt tokens that
requests needed computed, the count ``prefill_ms_per_ktok`` uses, in ms
(device trace, by the engine's program names)."""
from engtrace import per_prompt_token


def read(run):
    v = per_prompt_token(run)
    return None if v is None else v * 1e6
