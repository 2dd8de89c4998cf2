"""Layer part ``attn``: causal rotary attention with grouped kv heads
(Llama layout, ``x @ w``), the mixer of a dense decoder layer.

What a part module holds (the harness finds it as ``parts/<name>.py`` by
the name a configuration's ``pattern`` gives):

- ``KIND``: the program's layer kind it computes (``LayerSpec.mixer`` or
  ``.ffn``), which ``model.py`` compares with the program's pattern;
- ``PROGRAM``: where the program keeps each leaf in a layer's tree;
- ``shapes(m)``: its leaves and their canonical shapes for model dict
  ``m``; a mixer's pre-norm is ``ln1``, an FFN's ``ln2``;
- ``check(cfg, m)``: the program's ``ArchConfig`` fields it relies on
  that differ from ``m``, as ``{key: (configuration's, program's)}``;
- ``ref(xn, w, m, quant)``: its float32 forward on normed input, every
  weight matmul through ``reference._mm`` (so the fp8 control covers
  it). A mixer gets one sequence ``[L, D]``; an FFN gets rows
  ``[..., D]``, all at once;
- its counts: ``matmul_flops`` per token through its matmuls,
  ``key_flops`` per token per context key, ``other_flops`` per token
  (counted by hand), ``weight_bytes`` held, ``read_bytes`` of weights
  read per token, ``cache_bytes`` per token and ``state_bytes`` per slot;
- optionally ``IDS``, the draw ids of leaves no earlier part has
  (``weights.leaf_ids``), and ``RULES``, a leaf's ``(shift, scale)`` where
  the default draw does not fit (``weights.py``).

This part's leaves: ``ln1 [D]``, ``wq [D, Hq*hd]``, ``wk``/``wv
[D, Hkv*hd]``, ``wo [Hq*hd, D]``; model keys ``n_heads``, ``n_kv_heads``,
``head_dim``, ``rope_theta``. Counted: 2 operations per multiply-add of
its projections, and per context key 4 x Hq x hd (scores and the
weighted sum); its norm, rope and softmax are not counted. The cache
holds k and v, 2 x Hkv x hd values per token; no state per slot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from flops import dtype_bytes
from reference import HI, _mm

KIND = "attn"
PROGRAM = {"ln1": "ln1/scale", "wq": "attn/wq", "wk": "attn/wk",
           "wv": "attn/wv", "wo": "attn/wo"}


def shapes(m: dict) -> dict:
    D, hd = m["d_model"], m["head_dim"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return {"ln1": (D,), "wq": (D, q), "wk": (D, kv), "wv": (D, kv),
            "wo": (q, D)}


def check(cfg, m: dict) -> dict:
    bad = {k: (m[k], getattr(cfg, k)) for k in (
        "n_heads", "n_kv_heads", "head_dim", "rope_theta")
        if m[k] != getattr(cfg, k)}
    for field, want in (("pos_embed", "rope"), ("qk_norm", False),
                        ("attn_bias", False)):
        if getattr(cfg, field) != want:
            bad[field] = (want, getattr(cfg, field))
    return bad


def _rope(x, pos, theta):
    """x [L, H, hd]; rotate halves (Llama convention)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def ref(xn, w, m, quant):
    """xn [L, D] of one sequence -> [L, D]; causal, grouped kv heads."""
    L = xn.shape[0]
    hd, hq, hkv = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    pos = jnp.arange(L)
    q = _mm(xn, w["wq"], quant).reshape(L, hkv, hq // hkv, hd)
    k = _mm(xn, w["wk"], quant).reshape(L, hkv, hd)
    v = _mm(xn, w["wv"], quant).reshape(L, hkv, hd)
    q = _rope(q.reshape(L, hq, hd), pos, m["rope_theta"]).reshape(q.shape)
    k = _rope(k, pos, m["rope_theta"])
    s = jnp.einsum("qngh,knh->ngqk", q, k, precision=HI) * hd ** -0.5
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    o = jnp.einsum("ngqk,knh->qngh", jax.nn.softmax(s, -1), v, precision=HI)
    return _mm(o.reshape(L, hq * hd), w["wo"], quant)


def _params(m: dict) -> int:
    D, hd, hq, hkv = m["d_model"], m["head_dim"], m["n_heads"], \
        m["n_kv_heads"]
    return D * (hq + 2 * hkv) * hd + hq * hd * D


def matmul_flops(m: dict) -> int:
    return 2 * _params(m)


def key_flops(m: dict) -> int:
    return 4 * m["n_heads"] * m["head_dim"]


def other_flops(m: dict) -> int:
    return 0


def weight_bytes(m: dict) -> int:
    return (_params(m) + m["d_model"]) * dtype_bytes(m)


read_bytes = weight_bytes


def cache_bytes(m: dict) -> int:
    return 2 * m["n_kv_heads"] * m["head_dim"] * dtype_bytes(m)


def state_bytes(m: dict) -> int:
    return 0
