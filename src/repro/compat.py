"""Pallas ``interpret`` switch.

Every kernel takes ``interpret=None``, which resolves here: compiled on a
TPU backend, the Pallas interpreter elsewhere, so the CPU test suite runs
the same kernel call sites the chip compiles. The chip path never relies
on this switch for evidence: ``chip_smoke.py`` asserts that the compiled
decode program holds a ``tpu_custom_call``.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None``: the interpreter off-TPU, compiled on TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
