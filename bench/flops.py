"""Operations and bytes of the useful work, from the configuration's
shapes alone (model dict ``m`` of a configuration file).

Counted per token computed: 2 x the matmul weights it passes through
(q, k, v, o, gate, up, down in every layer) plus attention over its live
context, 4 x layers x q heads x head_dim x context (scores and the
weighted sum, 2 operations per multiply-add). The LM head (2 x d_model x
vocab) counts once per token sampled: every decode row, and a prefill's
last prompt position only. LP pairing changes the order of the work, not
its amount, so it does not enter.
"""
from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    D, F, hd = m["d_model"], m["d_ff"], m["head_dim"]
    hq, hkv = m["n_heads"], m["n_kv_heads"]
    return D * (hq + 2 * hkv) * hd + hq * hd * D + 3 * D * F


def head_flops(m: dict) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def attn_flops_per_key(m: dict) -> int:
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"]


def prefill_flops(m: dict, start: int, end: int) -> int:
    """Positions ``[start, end)`` of one prompt computed (``start``
    earlier positions come from the cache), one token sampled."""
    n = end - start
    keys = (start + 1 + end) * n // 2          # sum of contexts p + 1
    return (2 * m["n_layers"] * layer_matmul_params(m) * n
            + attn_flops_per_key(m) * keys + head_flops(m))


def decode_flops(m: dict, pos: int) -> int:
    """One decode row fed at position ``pos`` (context ``pos + 1``)."""
    return (2 * m["n_layers"] * layer_matmul_params(m)
            + attn_flops_per_key(m) * (pos + 1) + head_flops(m))


def dtype_bytes(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["dtype"]]


def weight_bytes(m: dict) -> int:
    D, V = m["d_model"], m["vocab_size"]
    top = D * V * (1 if m["tie_embeddings"] else 2) + D
    layer = layer_matmul_params(m) + 2 * D
    return (top + m["n_layers"] * layer) * dtype_bytes(m)


def kv_bytes_per_token(m: dict) -> int:
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * dtype_bytes(m)


def decode_bytes(m: dict, live_tokens: int) -> int:
    """Least bytes one decode step must read: every weight once (the
    embedding table only as rows, so not at all) plus the live kv."""
    D, V = m["d_model"], m["vocab_size"]
    emb = D * V * dtype_bytes(m) if not m["tie_embeddings"] else 0
    return weight_bytes(m) - emb + live_tokens * kv_bytes_per_token(m)
