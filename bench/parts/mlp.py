"""Layer part ``mlp``: the SwiGLU feed-forward of a dense decoder layer
(Llama layout, ``x @ w``; ``parts/attn.py`` says what a part module
holds).

Leaves: ``ln2 [D]``, ``w_gate``/``w_up [D, F]``, ``w_down [F, D]``;
model key ``d_ff``. Counted: 2 operations per multiply-add of its three
projections; its norm, SiLU and gate product are not counted. No cache,
no state.
"""
from __future__ import annotations

import jax

from flops import dtype_bytes
from reference import _mm

KIND = "mlp"
PROGRAM = {"ln2": "ln2/scale", "w_gate": "mlp/w_gate", "w_up": "mlp/w_up",
           "w_down": "mlp/w_down"}


def shapes(m: dict) -> dict:
    D, F = m["d_model"], m["d_ff"]
    return {"ln2": (D,), "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}


def check(cfg, m: dict) -> dict:
    bad = {"d_ff": (m["d_ff"], cfg.d_ff)} if m["d_ff"] != cfg.d_ff else {}
    for field, want in (("mlp_gated", True), ("mlp_act", "silu"),
                        ("mlp_bias", False)):
        if getattr(cfg, field) != want:
            bad[field] = (want, getattr(cfg, field))
    return bad


def ref(xn, w, m, quant):
    """Rows xn [..., D] -> [..., D]."""
    g = _mm(xn, w["w_gate"], quant)
    u = _mm(xn, w["w_up"], quant)
    return _mm(jax.nn.silu(g) * u, w["w_down"], quant)


def matmul_flops(m: dict) -> int:
    return 2 * 3 * m["d_model"] * m["d_ff"]


def key_flops(m: dict) -> int:
    return 0


def other_flops(m: dict) -> int:
    return 0


def weight_bytes(m: dict) -> int:
    return (3 * m["d_model"] * m["d_ff"] + m["d_model"]) * dtype_bytes(m)


read_bytes = weight_bytes


def cache_bytes(m: dict) -> int:
    return 0


def state_bytes(m: dict) -> int:
    return 0
