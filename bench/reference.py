"""The plain reference: the configuration's decoder in float32 ``jax.numpy``
at the highest matmul precision, with no cache, paging or batching tricks.

It imports nothing of the program. Its weights are drawn again from the
seed, one layer at a time inside that layer's jitted call
(``weights.layer_weights``), so nothing the program made is read and only
one layer's weights are on the device at once.

The model: token embedding; ``n_layers`` pre-norm blocks (RMSNorm,
rotary attention with grouped kv heads, SwiGLU MLP); a final RMSNorm and
the LM head (the embedding table when tied). Layer-parallel (LP) pairs
``(i, i + 1)`` from the configuration run side by side on the same input:
``a = x + A_i(N1_i x) + A_{i+1}(N1_{i+1} x)``, then
``y = a + F_i(N2_i a) + F_{i+1}(N2_{i+1} a)`` (arXiv:2502.02790).

``quant=True`` is the control: the same computation with both inputs of
every matmul rounded to float8 (e4m3; one scale per weight column and per
activation row): the precision a later change might be tempted to serve
in, one step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


def _rope(x, pos, theta):
    """x [L, H, hd]; rotate halves (Llama convention)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(xn, w, m, quant):
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


def _mlp(xn, w, quant):
    g = _mm(xn, w["w_gate"], quant)
    u = _mm(xn, w["w_up"], quant)
    return _mm(jax.nn.silu(g) * u, w["w_down"], quant)


def _weights(key, layer, m):
    return {n: a.astype(jnp.float32) for n, a in
            layer_weights(key, layer, m, jnp.dtype(m["dtype"])).items()}


def _group(x, key, layers, m, quant):
    """One block (one layer) or LP pair (two layers) over x [n, L, D]."""
    eps = m["rms_norm_eps"]
    ws = [_weights(key, i, m) for i in layers]
    # One sequence at a time through attention: its scores are [H, L, L].
    a = x + sum(jax.lax.map(
        lambda xs, w=w: _attention(_rms(xs, w["ln1"], eps), w, m, quant), x)
        for w in ws)
    return a + sum(_mlp(_rms(a, w["ln2"], eps), w, quant) for w in ws)


@functools.lru_cache(maxsize=None)
def _programs(mkey, quant):
    m = dict(mkey)
    dt = jnp.dtype(m["dtype"])

    def embed(key, seqs):
        return draw(key, TOP, "tok", top_shapes(m)["tok"], dt
                    ).astype(jnp.float32)[seqs]

    single = jax.jit(lambda x, key, i: _group(x, key, (i,), m, quant))
    pair = jax.jit(lambda x, key, i: _group(x, key, (i, i + 1), m, quant))

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
    keep = ("d_model", "n_layers", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab_size", "rope_theta", "rms_norm_eps",
            "tie_embeddings", "dtype")
    return tuple((k, m[k]) for k in keep) + (
        ("lp_pairs", tuple(map(tuple, m["lp_pairs"]))),)


def hidden_logits(m: dict, key, seqs, pos, *, quant=False):
    """Reference logits [n, P, V] (on the device) at positions ``pos`` of
    token rows ``seqs`` [n, L]."""
    embed, single, pair, head = _programs(_model_key(m), quant)
    starts = {i for i, _ in m["lp_pairs"]}
    x = embed(key, jnp.asarray(seqs))
    i = 0
    while i < m["n_layers"]:
        if i in starts:
            x = pair(x, key, jnp.int32(i))
            i += 2
        else:
            x = single(x, key, jnp.int32(i))
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
