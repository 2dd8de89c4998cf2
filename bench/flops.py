"""Operations and bytes of the useful work, from the configuration's
shapes alone (model dict ``m`` of a configuration file).

Each layer's parts (``bench/parts/``, named by the model's ``pattern``)
give its terms, and this module sums them over the layers. Counted per
token computed: 2 x the matmul weights it passes through in every layer
(q, k, v, o, gate, up, down in a dense layer), the operations a part
counts by hand besides (none in a dense layer), and per key of its live
context those of the parts that attend (4 x q heads x head_dim in each
attention layer: scores and the weighted sum, 2 operations per
multiply-add). The LM head (2 x d_model x vocab) counts once per token
sampled: every decode row, and a prefill's last prompt position only. LP
pairing changes the order of the work, not its amount, so it does not
enter.
"""
from __future__ import annotations

import spec

TERMS = ("matmul_flops", "key_flops", "other_flops", "weight_bytes",
         "read_bytes", "cache_bytes", "state_bytes")
#: id of a model dict -> (the dict, its sums): the harness counts every
#: decode row of every step, so the parts are summed once per model dict
#: (a configuration's dict is never changed once it is loaded).
_SUMS: dict = {}


def _sums(m: dict) -> dict:
    """Each part term summed over every layer: the pattern's positions,
    each times the layers it repeats to."""
    hit = _SUMS.get(id(m))
    if hit is None or hit[0] is not m:
        pat = spec.pattern(m)
        n, P = m["n_layers"], len(pat)
        hit = _SUMS[id(m)] = (m, {t: sum(
            ((n - j + P - 1) // P) * getattr(part, t)(m)
            for j, kind in enumerate(pat) for part in spec.parts_of(kind))
            for t in TERMS})
    return hit[1]


def layer_matmul_params(m: dict, layer: int = 0) -> int:
    """Matmul weights a token passes through in layer ``layer``."""
    return sum(part.matmul_flops(m) for part in
               spec.parts_of(spec.layer_kind(m, layer))) // 2


def head_flops(m: dict) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def attn_flops_per_key(m: dict) -> int:
    return _sums(m)["key_flops"]


def token_flops(m: dict) -> int:
    """Operations per token computed, before attention and the head."""
    s = _sums(m)
    return s["matmul_flops"] + s["other_flops"]


def prefill_flops(m: dict, start: int, end: int) -> int:
    """Positions ``[start, end)`` of one prompt computed (``start``
    earlier positions come from the cache), one token sampled."""
    n = end - start
    keys = (start + 1 + end) * n // 2          # sum of contexts p + 1
    return (token_flops(m) * n + attn_flops_per_key(m) * keys
            + head_flops(m))


def decode_flops(m: dict, pos: int) -> int:
    """One decode row fed at position ``pos`` (context ``pos + 1``)."""
    return (token_flops(m) + attn_flops_per_key(m) * (pos + 1)
            + head_flops(m))


def dtype_bytes(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["dtype"]]


def _top_bytes(m: dict) -> int:
    """The head (the embedding table when tied) and the final norm."""
    return (m["d_model"] * m["vocab_size"] + m["d_model"]) * dtype_bytes(m)


def weight_bytes(m: dict) -> int:
    emb = 0 if m["tie_embeddings"] else \
        m["d_model"] * m["vocab_size"] * dtype_bytes(m)
    return emb + _top_bytes(m) + _sums(m)["weight_bytes"]


def kv_bytes_per_token(m: dict) -> int:
    return _sums(m)["cache_bytes"]


def state_bytes_per_slot(m: dict) -> int:
    """Recurrent state a slot holds whatever its length (a Mamba layer's
    convolution window and scan state)."""
    return _sums(m)["state_bytes"]


def decode_bytes(m: dict, live_tokens: int) -> int:
    """Least bytes one decode step must read: every weight once (the
    embedding table only as rows, so not at all) plus the live kv. A
    layer with recurrent state also reads ``state_bytes_per_slot`` for
    each live slot, which this leaves out."""
    return (_top_bytes(m) + _sums(m)["read_bytes"]
            + live_tokens * kv_bytes_per_token(m))
