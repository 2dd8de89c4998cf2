"""Layer part ``mamba``: the Mamba-1 selective state-space mixer
(arXiv:2312.00752, its Algorithm 2 and reference code), a whole layer in
Falcon-Mamba and seven of eight mixers in Jamba (``parts/attn.py`` says
what a part module holds).

With ``E = ssm_expand * d_model`` inner channels, ``N = ssm_state``,
``K = ssm_conv`` and ``R = dt_rank``, on one sequence ``xn [L, D]``:

- ``[x; z] = xn @ w_in``, each ``[L, E]``;
- ``x = silu(conv(x))``: causal depthwise convolution of width ``K``
  with bias, tap ``j`` of ``conv_w [K, E]`` on position ``t - (K-1) + j``;
- ``[dt_raw; B; C] = x @ w_x`` (``R``, ``N``, ``N``);
- ``dt = softplus(dt_raw @ w_dt + dt_bias)``, ``A = -exp(A_log)``;
- ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t`` from ``h_0 = 0``, a plain
  sequential scan over ``[E, N]``; ``y_t = C_t . h_t + D x_t``;
- ``(y * silu(z)) @ w_out``.

This is what ``src/repro/model/ssm.py`` computes; it departs from the
paper only in precision: its in-projection, convolution output, x
projection and the scan's output round to the served bfloat16, and its
scan is a chunked associative scan (another order of the same sums).
Falcon-Mamba (arXiv:2410.05355) also RMS-normalises ``B``, ``C`` and
``dt``; neither the program nor this part does.

Leaves: ``ln1 [D]``, ``w_in [D, 2E]``, ``conv_w [K, E]``, ``conv_b [E]``,
``w_x [E, R + 2N]``, ``w_dt [R, E]``, ``dt_bias [E]``, ``A_log [E, N]``,
``D [E]``, ``w_out [E, D]``; model keys ``ssm_state``, ``ssm_conv``,
``ssm_expand``, ``dt_rank``. No FFN follows it in Falcon-Mamba.

Draw rules (``RULES``; the rest by default: matrices by fan-in, ``conv_w``
by its ``K`` taps as the program initialises it, ``D`` about 1 as the
paper's ones): ``conv_b`` about 0, sd 0.1, a small bias; ``dt_bias`` about
-4.6, sd 1, so that ``dt`` falls about 0.001-0.1, the range the paper
initialises it to (its ``softplus`` inverse of a log-uniform draw in
[0.001, 0.1]); ``A_log`` about 1.5, sd 0.75, so that ``-A`` spreads over
about 1-20 like the paper's ``1..N`` for ``N = 16``.

Counts per token: 2 operations per multiply-add of ``w_in``, ``w_x``,
``w_dt`` and ``w_out``; by hand, ``E * (2K + 6N + 4)`` others: the
convolution's ``K`` multiply-adds per channel; per channel and state
``dt A``, the product with ``B``, ``a h + b`` (2) and ``C . h`` (2);
per channel ``dt x``, ``D x``, its add and the gate's product (softplus,
exp and SiLU not counted, as softmax and SiLU are not in the dense
parts). No key flops and no kv cache. State per slot: the last ``K - 1``
inputs of the convolution in the served dtype and ``h`` in float32 (the
scan's carry, kept in float32 as the paper's code does),
``(K - 1) * E * dtype + E * N * 4`` bytes. Every weight is read once a
token, so ``read_bytes`` is ``weight_bytes``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from flops import dtype_bytes
from reference import HI, _mm
from weights import affine

KIND = "mamba"
PROGRAM = {"ln1": "ln1/scale", **{n: f"mamba/{n}" for n in (
    "w_in", "conv_w", "conv_b", "w_x", "w_dt", "dt_bias", "A_log", "D",
    "w_out")}}
IDS = {"w_in": 12, "conv_w": 13, "conv_b": 14, "w_x": 15, "w_dt": 16,
       "dt_bias": 17, "A_log": 18, "D": 19, "w_out": 20}
RULES = {"conv_b": affine(0.0, 0.1), "dt_bias": affine(-4.6, 1.0),
         "A_log": affine(1.5, 0.75)}


def _dims(m: dict):
    return (m["ssm_expand"] * m["d_model"], m["ssm_state"], m["ssm_conv"],
            m["dt_rank"])


def shapes(m: dict) -> dict:
    D = m["d_model"]
    E, N, K, R = _dims(m)
    return {"ln1": (D,), "w_in": (D, 2 * E), "conv_w": (K, E),
            "conv_b": (E,), "w_x": (E, R + 2 * N), "w_dt": (R, E),
            "dt_bias": (E,), "A_log": (E, N), "D": (E,), "w_out": (E, D)}


def check(cfg, m: dict) -> dict:
    return {k: (m[k], getattr(cfg, k)) for k in (
        "ssm_state", "ssm_conv", "ssm_expand", "dt_rank")
        if m[k] != getattr(cfg, k)}


def ref(xn, w, m, quant):
    """xn [L, D] of one sequence -> [L, D]."""
    L = xn.shape[0]
    E, N, K, R = _dims(m)
    xz = _mm(xn, w["w_in"], quant)
    x, z = xz[:, :E], xz[:, E:]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    x = jax.nn.silu(w["conv_b"] + sum(xp[j:j + L] * w["conv_w"][j]
                                      for j in range(K)))
    dbc = _mm(x, w["w_x"], quant)
    dt_raw, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    dt = jax.nn.softplus(_mm(dt_raw, w["w_dt"], quant) + w["dt_bias"])
    A = -jnp.exp(w["A_log"])

    def step(h, t):
        dt_t, x_t, b_t, c_t = t
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * b_t
        return h, jnp.dot(h, c_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((E, N), jnp.float32), (dt, x, B, C))
    y = y + w["D"] * x
    return _mm(y * jax.nn.silu(z), w["w_out"], quant)


def _matmul_params(m: dict) -> int:
    D = m["d_model"]
    E, N, K, R = _dims(m)
    return D * 2 * E + E * (R + 2 * N) + R * E + E * D


def matmul_flops(m: dict) -> int:
    return 2 * _matmul_params(m)


def key_flops(m: dict) -> int:
    return 0


def other_flops(m: dict) -> int:
    E, N, K, _ = _dims(m)
    return E * (2 * K + 6 * N + 4)


def weight_bytes(m: dict) -> int:
    E, N, K, _ = _dims(m)
    vectors = K * E + 3 * E + E * N + m["d_model"]
    return (_matmul_params(m) + vectors) * dtype_bytes(m)


read_bytes = weight_bytes


def cache_bytes(m: dict) -> int:
    return 0


def state_bytes(m: dict) -> int:
    E, N, K, _ = _dims(m)
    return (K - 1) * E * dtype_bytes(m) + E * N * 4
