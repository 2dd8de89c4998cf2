"""The pinned-tile prefill attention core visits only the live kv tiles.

``attention._chunked_core`` under a pinned kv tile (impl ``chunked:<kb>``)
stops each q tile's kv loop after the last tile any of its queries can
see. A fully masked tile after a live one is a bitwise no-op, so the
live-tile loop must equal a loop over EVERY tile bit for bit. The
reference below is that full loop, written out here so that it does not
share code with the core under test.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.model import attention as A

NEG = A.NEG_INF


def full_tile_core(q, k, v, *, kind, prefix_len, q0, qb, kb):
    """Online softmax over every ``kb``-wide kv tile, dead or not."""
    B, S0, Hk, g, hd = q.shape
    T0 = k.shape[1]
    qb = min(qb, S0)
    pq, pk = (-S0) % qb, (-T0) % kb
    q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    S, T = S0 + pq, T0 + pk
    nq, nk = S // qb, T // kb
    qt = q.reshape(B, nq, qb, Hk, g, hd).transpose(1, 0, 3, 4, 2, 5)
    kt = k.reshape(B, nk, kb, Hk, hd).transpose(1, 0, 3, 2, 4)
    vt = v.reshape(B, nk, kb, Hk, hd).transpose(1, 0, 3, 2, 4)
    per_row = getattr(q0, "ndim", 0) > 0

    def q_step(_, x):
        qi, qtile = x
        qpos = (q0[:, None] if per_row else q0) + qi * qb + jnp.arange(qb)

        def kv_step(c, y):
            m, l, acc = c
            ki, kk, vv = y
            kpos = ki * kb + jnp.arange(kb)
            msk = A.tile_mask(kind, qpos, kpos, prefix_len=prefix_len)
            msk = msk & (kpos < T0)[None, :]
            s = jnp.einsum("bngqh,bnkh->bngqk", qtile.astype(jnp.float32),
                           kk.astype(jnp.float32)) * hd ** -0.5
            bias = jnp.where(msk, 0.0, NEG)
            s = s + (bias[:, None, None] if per_row
                     else bias[None, None, None])
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bngqk,bnkh->bngqh", p, vv.astype(jnp.float32))
            return (m_new, l, acc), None

        c0 = (jnp.full((B, Hk, g, qb), NEG, jnp.float32),
              jnp.zeros((B, Hk, g, qb), jnp.float32),
              jnp.zeros((B, Hk, g, qb, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, c0, (jnp.arange(nk), kt, vt))
        return None, (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    _, tiles = jax.lax.scan(q_step, None, (jnp.arange(nq), qt))
    return tiles.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, Hk, g, hd)[:, :S0]


def _qkv(B, S, T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, 2, 2, 8), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, 2, 8), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, 2, 8), jnp.float32)
    return q, k, v


# (name, B, S, T, q0, qb, kind, prefix_len). Per-row q0 is the bucketed
# ctx-aware prefill (cold rows at 0, radix hits at their ctx length, keys
# = [ctx | suffix | junk]); scalar q0 the exact (0) and suffix (ctx)
# programs.
CASES = [
    ("rows_all_cold", 3, 16, 16 + 64, np.array([0, 0, 0]), 8, "attn", 0),
    ("rows_mixed_hits", 3, 16, 16 + 64, np.array([0, 48, 16]), 8, "attn", 0),
    ("rows_two_q_tiles", 2, 32, 32 + 48, np.array([16, 0]), 16, "attn", 0),
    ("exact", 1, 37, 37, 0, 16, "attn", 0),
    ("suffix", 1, 21, 32 + 21, 32, 8, "attn", 0),
    ("t_not_multiple_of_kb", 2, 13, 13 + 22, 22, 8, "attn_global", 0),
    ("prefix_lm", 2, 24, 24, 0, 8, "attn", 20),
    ("bidir", 2, 12, 40, 0, 8, "attn_bidir", 0),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_live_tile_core_is_bit_equal_to_the_full_loop(case):
    _, B, S, T, q0, qb, kind, prefix_len = case
    q, k, v = _qkv(B, S, T)
    q0j = jnp.asarray(q0, jnp.int32) if np.ndim(q0) else q0
    got = jax.jit(lambda q, k, v, o: A.attention_core(
        q, k, v, kind=kind, prefix_len=prefix_len, q0=o, qb=qb,
        impl="chunked:8"))(q, k, v, q0j)
    want = jax.jit(lambda q, k, v, o: full_tile_core(
        q, k, v, kind=kind, prefix_len=prefix_len, q0=o, qb=qb,
        kb=8))(q, k, v, q0j)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _brute_live(S, T, qb, kb, q0s, kind, prefix_len):
    """Tiles some query of each q tile can see under the mask itself."""
    qb = min(qb, S)
    nq, nk = -(-S // qb), -(-T // kb)
    live = 0
    for qi in range(nq):
        seen = []
        for ki in range(nk):
            kpos = np.arange(ki * kb, (ki + 1) * kb)
            vis = False
            for o in q0s:
                qpos = o + qi * qb + np.arange(qb)
                vis |= bool(A.tile_mask(kind, qpos, kpos,
                                        prefix_len=prefix_len).any())
            seen.append(vis)
        # The loop runs over a prefix of tiles: up to the last seen one.
        live += max(i for i, s in enumerate(seen) if s) + 1
    return live, nq * nk


@pytest.mark.parametrize("kind,prefix_len", [("attn", 0), ("attn", 13),
                                             ("attn_bidir", 0)])
def test_live_kv_tiles_matches_the_mask(kind, prefix_len):
    for S, T, qb, kb, q0s in itertools.product(
            (5, 16, 24), (24, 40), (8, 16), (8, 16), ([0], [0, 16], [16])):
        if T < S + max(q0s):
            continue
        want = _brute_live(S, T, qb, kb, q0s, kind, prefix_len)
        assert A.live_kv_tiles(S, T, qb, kb, max(q0s), kind,
                               prefix_len) == want, (S, T, qb, kb, q0s)


def test_live_kv_tiles_of_a_cold_bucket_launch():
    # Yi-6B's cell: a 512-token cold bucket row behind 127 ctx pages of 16.
    live, total = A.live_kv_tiles(512, 127 * 16 + 512, 512, 16, 0, "attn")
    assert (live, total) == (32, 159)


def test_engine_counts_live_and_total_tiles_per_launch():
    """A prefix-on engine's bucket launches run the ctx-aware program
    (3 ctx pages of 8 here): a cold-only launch visits fewer tiles than
    the full loop, and a launch carrying a radix hit visits more than an
    all-cold launch of the same bucket, by the helper's numbers."""
    from repro.model import transformer as T
    from repro.serve import PagedEngine, PagedServeConfig
    from repro.serve import bucketing as BK

    from _helpers import tiny

    cfg = tiny(n_layers=2)
    ms = T.build_structure(cfg, tp=1)
    params = T.init_params(ms, jax.random.PRNGKey(0))
    eng = PagedEngine(params, ms, PagedServeConfig(
        n_slots=4, page_size=8, n_pages=21, max_len=32,
        cache_dtype=jnp.float32, prefix_cache=True))
    ctx_keys = 3 * 8

    def prompt(i, n):
        return np.asarray(jax.random.randint(jax.random.PRNGKey(i), (n,), 0,
                                             cfg.vocab_size))

    def launch(*prompts):
        c0 = dict(eng.counters)
        for p in prompts:
            eng.add_request(p, 3)
        eng.drain()
        return (eng.counters["kv_tiles_live"] - c0["kv_tiles_live"],
                eng.counters["kv_tiles_total"] - c0["kv_tiles_total"])

    def helper(bucket, q0_max):
        return A.live_kv_tiles(bucket, ctx_keys + bucket, A.Q_TILE,
                               BK.PREFILL_KV_TILE, q0_max, "attn")

    cold = launch(prompt(1, 7))                        # bucket 8
    assert cold == helper(8, 0) == (1, 2)
    shared = prompt(2, 16)                             # two whole pages
    donor = np.concatenate([shared, prompt(3, 8)])     # bucket 32
    assert launch(donor) == helper(32, 0) == (2, 4)
    g0 = eng.counters["bucket_groups"]
    mixed = launch(np.concatenate([shared, prompt(4, 6)]), prompt(5, 7))
    assert eng.counters["bucket_groups"] == g0 + 1     # one shared launch
    assert eng.counters["prefix_hits"] == 1
    assert mixed == helper(8, 16) == (2, 2)
    assert mixed[0] > cold[0]
    # The per-step gauges carry the same launches.
    series = eng.telemetry.gauge_series
    assert sum(v for _, v in series["kv_tiles_live_step"]) == 5
    assert sum(v for _, v in series["kv_tiles_total_step"]) == 8
