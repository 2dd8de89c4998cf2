"""The plain reference: the configuration's decoder in float32 ``jax.numpy``
at the highest matmul precision, with no cache, paging or batching tricks.

It imports nothing of the program. Its weights are drawn again from the
seed, one layer at a time inside that layer's jitted call
(``weights.layer_weights``), so nothing the program made is read and only
one layer's weights are on the device at once.

The model: token embedding; ``n_layers`` pre-norm blocks, each a mixer
``A`` and an FFN ``F`` or none, computed by the layer's parts
(``bench/parts/``, named by the configuration's ``pattern``; by default
rotary attention with grouped kv heads and a SwiGLU MLP) between RMSNorms;
a final RMSNorm and the LM head (the embedding table when tied).
Layer-parallel (LP) pairs ``(i, i + 1)`` from the configuration run side
by side on the same input: ``a = x + A_i(N1_i x) + A_{i+1}(N1_{i+1} x)``,
then ``y = a + F_i(N2_i a) + F_{i+1}(N2_{i+1} a)`` (arXiv:2502.02790); a
single layer is the same with one term, and ``y = a`` without an FFN.

``quant=True`` is the control: the same computation with both inputs of
every matmul rounded to float8 (e4m3; one scale per weight column and per
activation row): the precision a later change might be tempted to serve
in, one step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import spec
from weights import TOP, draw, layer_weights, top_shapes

HI = jax.lax.Precision.HIGHEST


def fp8(x, axis):
    """float8 (e4m3) round trip, one scale per slice along ``axis``."""
    s = jnp.maximum(jnp.abs(x).max(axis=axis, keepdims=True), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x [..., K] @ w [K, N] in float32; the control rounds both to fp8."""
    if quant:
        x, w = fp8(x, -1), fp8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def _weights(key, layer, m, kind):
    return {n: a.astype(jnp.float32) for n, a in layer_weights(
        key, layer, m, jnp.dtype(m["dtype"]), kind).items()}


def _group(x, key, layers, kinds, m, quant):
    """One block (one layer) or LP pair (two layers) over x [n, L, D];
    ``kinds``: each layer's ``(mixer, ffn)``."""
    eps = m["rms_norm_eps"]
    ws = [_weights(key, i, m, k) for i, k in zip(layers, kinds)]
    parts = [spec.parts_of(k) for k in kinds]      # [mixer] or [mixer, ffn]
    # One sequence at a time through a mixer: attention's scores are
    # [H, L, L], a scan's state runs along the sequence.
    a = x + sum(jax.lax.map(
        lambda xs, w=w, p=p: p[0].ref(_rms(xs, w["ln1"], eps), w, m, quant),
        x) for w, p in zip(ws, parts))
    ffns = [(w, p[1]) for w, p in zip(ws, parts) if len(p) > 1]
    if not ffns:
        return a
    return a + sum(f.ref(_rms(a, w["ln2"], eps), w, m, quant)
                   for w, f in ffns)


@functools.lru_cache(maxsize=None)
def _programs(mkey, quant):
    m = json.loads(mkey)
    dt = jnp.dtype(m["dtype"])

    def embed(key, seqs):
        return draw(key, TOP, "tok", top_shapes(m)["tok"], dt
                    ).astype(jnp.float32)[seqs]

    single = jax.jit(lambda x, key, i, k: _group(x, key, (i,), k, m, quant),
                     static_argnums=3)
    pair = jax.jit(lambda x, key, i, k: _group(x, key, (i, i + 1), k, m,
                                               quant), static_argnums=3)

    def head(key, x, pos):
        """Hidden [n, L, D] at ``pos`` [n, P] -> logits [n, P, V]."""
        h = jnp.take_along_axis(x, pos[..., None], axis=1)
        fn = draw(key, TOP, "final_norm", (m["d_model"],), dt)
        h = _rms(h, fn.astype(jnp.float32), m["rms_norm_eps"])
        if m["tie_embeddings"]:
            w = draw(key, TOP, "tok", top_shapes(m)["tok"], dt).T
        else:
            w = draw(key, TOP, "head", top_shapes(m)["head"], dt)
        return _mm(h, w.astype(jnp.float32), quant)

    return jax.jit(embed), single, pair, jax.jit(head)


def _model_key(m):
    """The whole model dict, pattern and each part's keys with it."""
    return json.dumps(m, sort_keys=True)


def hidden_logits(m: dict, key, seqs, pos, *, quant=False):
    """Reference logits [n, P, V] (on the device) at positions ``pos`` of
    token rows ``seqs`` [n, L]."""
    embed, single, pair, head = _programs(_model_key(m), quant)
    starts = {i for i, _ in m["lp_pairs"]}
    x = embed(key, jnp.asarray(seqs))
    i = 0
    while i < m["n_layers"]:
        if i in starts:
            x = pair(x, key, jnp.int32(i), (spec.layer_kind(m, i),
                                            spec.layer_kind(m, i + 1)))
            i += 2
        else:
            x = single(x, key, jnp.int32(i), (spec.layer_kind(m, i),))
            i += 1
    return head(key, x, jnp.asarray(pos))


@jax.jit
def _gap_stats(ref, served, ctl):
    """Per position: gap of the served token below the reference's best,
    and gap of the control's first choice, both in units of the row's
    standard deviation."""
    top = ref.max(-1)
    sd = ref.std(-1)
    pick = jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    gap = (top - pick) / sd
    if ctl is None:
        return gap, None
    c = jnp.take_along_axis(ref, ctl.argmax(-1)[..., None], -1)[..., 0]
    return gap, (top - c) / sd


def check_rows(requests, max_len: int):
    """Token rows, positions and served tokens for the reference: each
    request is ``(prompt, served)``; position ``len(prompt) - 1 + j``
    predicts ``served[j]``. Rows pad to ``max_len`` and positions to the
    longest served run (``mask`` marks the real ones)."""
    n = len(requests)
    P = max(len(o) for _, o in requests)
    seqs = np.zeros((n, max_len), np.int32)
    pos = np.zeros((n, P), np.int32)
    served = np.zeros((n, P), np.int32)
    mask = np.zeros((n, P), bool)
    for r, (p, o) in enumerate(requests):
        row = np.concatenate([p, o[:-1]])
        seqs[r, :len(row)] = row
        k = len(o)
        pos[r, :k] = len(p) - 1 + np.arange(k)
        served[r, :k] = o
        mask[r, :k] = True
    return seqs, pos, served, mask


def gaps(m: dict, seed: int, requests, max_len: int, *, control=False):
    """Gap (in sigma) of every served token below the float32 reference's
    top logit; with ``control``, also the gap of the fp8 control's first
    choice at the same positions. Returns ``(gaps, control_gaps|None)``,
    each a flat numpy array over the served positions."""
    from weights import seed_key
    key = seed_key(seed)
    seqs, pos, served, mask = check_rows(requests, max_len)
    ref = hidden_logits(m, key, seqs, pos)
    ctl = hidden_logits(m, key, seqs, pos, quant=True) if control else None
    g, c = _gap_stats(ref, jnp.asarray(served), ctl)
    g = np.asarray(g)[mask]
    return g, (np.asarray(c)[mask] if control else None)
