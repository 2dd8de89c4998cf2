"""Sharded paged serving: the tp>1 PagedEngine and its supporting layers.

Fast units (single device): the sharded pool's layout/pspecs mirror the
ring cache's model-axis rules, the paged kernels' head_map scalar-prefetch
selection agrees with slicing the pool, and ``validate_paged_support``
rejects kv-head counts the model axis cannot cut evenly.

Slow subprocess tests (8 host devices): a tp=2 engine under staggered
continuous batching is BIT-identical to the tp=1 engine and to one-shot
``sharded_generate``; one sharded paged decode step matches the sharded
ring step; the Pallas in-kernel head selection agrees with the XLA gather
path under replicated kv (tp > n_kv); and the prefix cache STAYS ON under
tp>1 — radix-hit suffix prefills on the sharded engine are bit-identical
to cold full prefills and to one-shot ``sharded_generate``.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core.lp import plan_range
from repro.kernels.decode_attention import (decode_attention_paged,
                                            decode_attention_pair_paged)
from repro.model import attention as A
from repro.model import blocks as BL
from repro.model import transformer as T
from repro.parallel.context import ParallelContext
from repro.serve import paged_cache as PG

from _helpers import run_multidevice, tiny

KEY = jax.random.PRNGKey(0)


@dataclasses.dataclass(frozen=True)
class _FixedRank(ParallelContext):
    """ParallelContext pinned to one rank — lets a single-device unit test
    evaluate the per-rank kv in-gather for every rank without shard_map."""
    rank: int = 0

    def tp_index(self):
        return jnp.int32(self.rank)


# ---------------------------------------------------------------------------
# Fast units
# ---------------------------------------------------------------------------

def test_sharded_pool_layout_and_pspecs():
    """The paged pool under tp=2 keeps the ring cache's partition rules:
    kv-sharded head axis carries "model" at the SAME axis position (pages
    replace [B, L] without moving any sharded dim); state entries keep
    their ring pspecs; pool shapes stay GLOBAL (hkv_global heads)."""
    cfg = tiny(n_layers=4)                      # 4 q heads, 4 kv heads
    ms = T.build_structure(cfg, plan=plan_range(cfg, 0, 4), tp=2)
    abs_, ps_ = PG.paged_cache_meta(ms, n_slots=2, n_pages=9, page_size=8,
                                    dtype=jnp.float32)
    dims = ms.dims
    assert dims.kv_sharded
    for seg_abs, seg_ps in zip(abs_, ps_):
        assert set(seg_abs.keys()) == {"k", "v"}
        for name in ("k", "v"):
            # [count, 2, n_pages, Hkv_global, ps, hd]
            assert seg_abs[name].shape[1:] == (2, 9, dims.hkv_global, 8,
                                               dims.hd)
            spec = tuple(seg_ps[name])
            assert spec[3] == "model", spec        # head axis sharded
            assert all(s is None for i, s in enumerate(spec) if i != 3)

    # Replicated kv (tp > n_kv): pool replicated, no model axis anywhere.
    cfg_r = dataclasses.replace(cfg, n_kv_heads=2)
    ms_r = T.build_structure(cfg_r, plan=plan_range(cfg_r, 0, 4), tp=4)
    assert not ms_r.dims.kv_sharded
    _, ps_r = PG.paged_cache_meta(ms_r, n_slots=2, n_pages=9, page_size=8,
                                  dtype=jnp.float32)
    for seg_ps in ps_r:
        for name in ("k", "v"):
            assert all(s is None for s in tuple(seg_ps[name]))


def test_paged_kernel_head_map_selects_stored_head():
    """head_map=[i] must equal running the identity kernel on the pool
    sliced to head i — the in-kernel form of select_local_kv."""
    B, n_pages, ps, Hkv, hd, n_pg = 2, 7, 8, 3, 16, 3
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (n_pages, Hkv, ps, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), k.shape)
    q = jax.random.normal(jax.random.fold_in(KEY, 3), (B, 1, 4, hd))
    bt = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
    t = jnp.array([13, 20], jnp.int32)
    for h in range(Hkv):
        got = decode_attention_paged(q, k, v, bt, t,
                                     head_map=jnp.array([h], jnp.int32))
        ref = decode_attention_paged(q, k[:, h:h + 1], v[:, h:h + 1], bt, t)
        assert jnp.array_equal(got, ref), h


def test_paged_pair_kernel_head_map_matches_sliced_pool():
    """Pair variant: one head_map serves both halves; multi-entry maps
    (the per-head TP mode) permute heads exactly like pool gathering."""
    B, n_pages, ps, Hkv, hd, n_pg = 2, 5, 4, 2, 16, 2
    k = jax.random.normal(jax.random.fold_in(KEY, 4),
                          (2, n_pages, Hkv, ps, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 5), k.shape)
    q = jax.random.normal(jax.random.fold_in(KEY, 6), (2, B, 2, 1, hd))
    bt = jnp.array([[1, 2], [3, 4]], jnp.int32)
    t = jnp.array([5, 7], jnp.int32)
    hm = jnp.array([1, 0], jnp.int32)              # swap the two heads
    got = decode_attention_pair_paged(q, k, v, bt, t, head_map=hm)
    ref = decode_attention_pair_paged(q, k[:, :, ::-1], v[:, :, ::-1], bt, t)
    assert jnp.array_equal(got, ref)


def test_validate_paged_support_rejects_indivisible_kv():
    """n_kv % tp != 0 with sharded kv heads must fail AT VALIDATION with a
    message naming the fix, not inside the kernel index map; replicated kv
    (tp > n_kv) and dividing configs stay accepted."""
    cfg = dataclasses.replace(tiny(n_layers=2), n_heads=4, n_kv_heads=3)
    ms = T.build_structure(cfg, tp=2)              # 3 kv heads over 2 ranks
    with pytest.raises(ValueError, match="does not divide"):
        PG.validate_paged_support(ms, 64)
    ok = T.build_structure(tiny(n_layers=2), tp=2)          # 4 over 2
    PG.validate_paged_support(ok, 64)
    repl = T.build_structure(cfg, tp=4)            # replicated: 3 < 4
    PG.validate_paged_support(repl, 64)


def test_fold_ctx_kv_sharded_pool_is_identity_on_heads():
    """kv-SHARDED pool: ``gather_ctx`` inside shard_map already hands each
    rank its LOCAL head shard, so the fold must be pure layout (pair-major
    head fold), bit-identical to folding the shard by hand."""
    cfg = tiny(n_layers=4)                          # 4 q heads, 4 kv heads
    ms = T.build_structure(cfg, plan=plan_range(cfg, 0, 4), tp=2)
    dims = ms.dims
    assert dims.kv_sharded
    group = ms.segments[0].group
    B, Tc = 2, 8
    ck = jax.random.normal(jax.random.fold_in(KEY, 10),
                           (2, B, Tc, dims.hkv, dims.hd))
    cv = jax.random.normal(jax.random.fold_in(KEY, 11), ck.shape)
    ks, vs = BL._fold_ctx_kv({"k": ck, "v": cv}, dims, ParallelContext(),
                             group=group)
    ref_k = jnp.moveaxis(ck, 0, 2).reshape(B, Tc, 2 * dims.hkv, dims.hd)
    ref_v = jnp.moveaxis(cv, 0, 2).reshape(B, Tc, 2 * dims.hkv, dims.hd)
    assert jnp.array_equal(ks, ref_k) and jnp.array_equal(vs, ref_v)

    # The trace-time audit: a ctx tree carrying the GLOBAL head count on a
    # sharded-kv rank is mis-sharded and must fail loudly.
    bad = jax.random.normal(jax.random.fold_in(KEY, 12),
                            (2, B, Tc, dims.hkv_global, dims.hd))
    with pytest.raises(AssertionError, match="kv layout"):
        BL._fold_ctx_kv({"k": bad, "v": bad}, dims, ParallelContext(),
                        group=group)


def test_fold_ctx_kv_replicated_pool_ingathers_rank_head():
    """REPLICATED pool (n_kv < tp): every rank holds all stored heads and
    the fold in-gathers this rank's head — the same selection the paged
    decode kernel performs via ``paged_head_map``, checked against slicing
    the pool by hand for EVERY rank."""
    cfg = dataclasses.replace(tiny(n_layers=4), n_kv_heads=2)
    tp = 4
    ms = T.build_structure(cfg, plan=plan_range(cfg, 0, 4), tp=tp)
    dims = ms.dims
    assert not dims.kv_sharded
    Hk_eff, _ = A.core_layout(dims)
    group = ms.segments[0].group
    B, Tc = 2, 8
    ck = jax.random.normal(jax.random.fold_in(KEY, 13),
                           (2, B, Tc, dims.hkv, dims.hd))
    cv = jax.random.normal(jax.random.fold_in(KEY, 14), ck.shape)
    for r in range(tp):
        ks, vs = BL._fold_ctx_kv({"k": ck, "v": cv}, dims,
                                 _FixedRank(rank=r), group=group)
        assert ks.shape == (B, Tc, 2 * Hk_eff, dims.hd)
        h = min(r * dims.hq // dims.group, dims.hkv - 1)
        sel_k = ck[:, :, :, h:h + Hk_eff]
        sel_v = cv[:, :, :, h:h + Hk_eff]
        ref_k = jnp.moveaxis(sel_k, 0, 2).reshape(B, Tc, 2 * Hk_eff, dims.hd)
        ref_v = jnp.moveaxis(sel_v, 0, 2).reshape(B, Tc, 2 * Hk_eff, dims.hd)
        assert jnp.array_equal(ks, ref_k) and jnp.array_equal(vs, ref_v), r


# ---------------------------------------------------------------------------
# Multi-device (subprocess) parity
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_tp2_engine_bit_identical_to_tp1_and_sharded_one_shot():
    """Staggered tp=2 continuous batching == tp=1 engine == one-shot
    sharded_generate, bitwise per request; accounting drains; the prefix
    cache stays LIVE under the mesh."""
    out = run_multidevice(r"""
import jax, jax.numpy as jnp, numpy as np, json
from repro.configs import get_config, reduced_config
from repro.core.lp import LPPlan, plan_range
from repro.model import transformer as T
from repro.serve import (PagedEngine, PagedServeConfig, ServeConfig,
                         sharded_generate)

cfg = reduced_config(get_config("tinyllama-1.1b"), n_layers=6)
plan = LPPlan(plan_range(cfg, 0, 6).pairs[:3])
ms1 = T.build_structure(cfg, plan=plan, tp=1)
ms2 = T.build_structure(cfg, plan=plan, tp=2)
params = T.init_params(ms1, jax.random.PRNGKey(0))
mesh = jax.make_mesh((1, 2), ("data", "model"))
psv = PagedServeConfig(n_slots=4, page_size=8, n_pages=33, max_len=64,
                       cache_dtype=jnp.float32)
key = jax.random.PRNGKey(7)
prompts = [np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                         (L,), 0, cfg.vocab_size))
           for i, L in enumerate([6, 9, 12, 8, 11, 7])]
res = {}
for name, ms, mk in (("tp1", ms1, None), ("tp2", ms2, mesh)):
    eng = PagedEngine(params, ms, psv, mesh=mk)
    rids = [eng.add_request(p, 10) for p in prompts[:4]]
    eng.step(); eng.step()                       # staggered admission
    rids += [eng.add_request(p, 10) for p in prompts[4:]]
    eng.drain()
    assert eng.pool.live == 0
    assert eng.pool.allocated_total == eng.pool.freed_total > 0
    res[name] = eng
same = all((res["tp1"].results[r] == res["tp2"].results[r]).all()
           for r in res["tp1"].results)
sv = ServeConfig(max_len=64, temperature=0.0, cache_dtype=jnp.float32)
one_shot = all(
    (res["tp2"].results[i] ==
     sharded_generate(params, prompts[i][None], 10, ms=ms2, mesh=mesh,
                      sv=sv)[0]).all()
    for i in range(3))
psv_px = PagedServeConfig(n_slots=4, page_size=8, n_pages=33, max_len=64,
                          cache_dtype=jnp.float32, prefix_cache=True)
prefix_on = PagedEngine(params, ms2, psv_px, mesh=mesh).prefix is not None
print("RESULT " + json.dumps({"same": same, "one_shot": one_shot,
                              "prefix_on": prefix_on}))
""")
    res = json.loads([l for l in out.splitlines()
                      if l.startswith("RESULT")][0][7:])
    assert res == {"same": True, "one_shot": True, "prefix_on": True}, res


@pytest.mark.slow
def test_tp2_prefix_hit_bit_identical_to_cold_and_one_shot():
    """Sharded radix sharing end to end: a donor family prompt, then
    radix-HIT members through the tp=2 prefix-on engine — bit-identical to
    the tp=1 prefix-on engine, to a prefix-OFF tp=2 engine (cold prefills),
    and to one-shot ``sharded_generate``; hit suffixes ride the bucket
    path (no exact-length suffix program is ever compiled)."""
    out = run_multidevice(r"""
import jax, jax.numpy as jnp, numpy as np, json
from repro.configs import get_config, reduced_config
from repro.core.lp import LPPlan, plan_range
from repro.model import transformer as T
from repro.serve import (PagedEngine, PagedServeConfig, ServeConfig,
                         sharded_generate)

cfg = reduced_config(get_config("tinyllama-1.1b"), n_layers=6)
plan = LPPlan(plan_range(cfg, 0, 6).pairs[:3])
ms1 = T.build_structure(cfg, plan=plan, tp=1)
ms2 = T.build_structure(cfg, plan=plan, tp=2)
params = T.init_params(ms1, jax.random.PRNGKey(0))
mesh = jax.make_mesh((1, 2), ("data", "model"))
psv = PagedServeConfig(n_slots=4, page_size=8, n_pages=33, max_len=64,
                       cache_dtype=jnp.float32, prefix_cache=True)
key = jax.random.PRNGKey(11)
shared = np.asarray(jax.random.randint(jax.random.fold_in(key, 0), (16,),
                                       0, cfg.vocab_size))
tails = [np.asarray(jax.random.randint(jax.random.fold_in(key, 1 + i),
                                       (8,), 0, cfg.vocab_size))
         for i in range(3)]
prompts = [np.concatenate([shared, t]) for t in tails]
res, rids = {}, {}
for name, ms, mk in (("tp1", ms1, None), ("tp2", ms2, mesh)):
    eng = PagedEngine(params, ms, psv, mesh=mk)
    r = [eng.add_request(prompts[0], 8)]       # donor: cold full prefill
    eng.drain()                                # donates the shared pages
    r += [eng.add_request(p, 8) for p in prompts[1:]]   # radix hits
    eng.drain()
    assert eng.counters["prefix_hits"] == 2, dict(eng.counters)
    assert eng.counters["suffix_prefills"] == 2, dict(eng.counters)
    assert not any(k[1] in ("prefill_full", "prefill_suffix")
                   for k in eng.telemetry.compiles), (
        dict(eng.telemetry.compiles))
    assert sum(1 for k in eng.telemetry.compiles
               if k[1] == "prefill_bucket") <= len(eng._buckets)
    res[name], rids[name] = eng, r
tp_same = all((res["tp1"].results[a] == res["tp2"].results[b]).all()
              for a, b in zip(rids["tp1"], rids["tp2"]))
psv_off = PagedServeConfig(n_slots=4, page_size=8, n_pages=33, max_len=64,
                           cache_dtype=jnp.float32)
eng_c = PagedEngine(params, ms2, psv_off, mesh=mesh)
crids = [eng_c.add_request(p, 8) for p in prompts]
eng_c.drain()
assert eng_c.counters["suffix_prefills"] == 0
cold_same = all((eng_c.results[c] == res["tp2"].results[b]).all()
                for c, b in zip(crids, rids["tp2"]))
sv = ServeConfig(max_len=64, temperature=0.0, cache_dtype=jnp.float32)
one_shot = all(
    (res["tp2"].results[b] ==
     sharded_generate(params, prompts[i][None], 8, ms=ms2, mesh=mesh,
                      sv=sv)[0]).all()
    for i, b in enumerate(rids["tp2"]))
print("RESULT " + json.dumps({"tp_same": tp_same, "cold_same": cold_same,
                              "one_shot": one_shot}))
""")
    res = json.loads([l for l in out.splitlines()
                      if l.startswith("RESULT")][0][7:])
    assert res == {"tp_same": True, "cold_same": True,
                   "one_shot": True}, res


@pytest.mark.slow
def test_sharded_paged_step_matches_sharded_ring_step():
    """One decode step, same state: the shard_map'd PAGED program (pool +
    block tables) and the shard_map'd RING program pick the same next
    token from logits that agree to float tolerance."""
    out = run_multidevice(r"""
import jax, jax.numpy as jnp, numpy as np, json
from repro.configs import get_config, reduced_config
from repro.core.lp import LPPlan, plan_range
from repro.model import transformer as T
from repro.serve import PagedEngine, PagedServeConfig, ServeConfig
from repro.serve.engine import make_sharded_prefill, make_sharded_serve_step

cfg = reduced_config(get_config("tinyllama-1.1b"), n_layers=4)
plan = LPPlan(plan_range(cfg, 0, 4).pairs[:2])
ms = T.build_structure(cfg, plan=plan, tp=2)
params = T.init_params(ms, jax.random.PRNGKey(0))
mesh = jax.make_mesh((1, 2), ("data", "model"))
prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (9,), 0,
                                       cfg.vocab_size))
MAXLEN = 32

# Ring: sharded prefill (sp off, exact length) + one sharded serve step.
sv = ServeConfig(max_len=MAXLEN, temperature=0.0, cache_dtype=jnp.float32)
pre, _, _ = make_sharded_prefill(ms, mesh, sv, batch=1, prompt_len=9,
                                 sp=False)
step, _, _, _ = make_sharded_serve_step(ms, mesh, sv, batch=1,
                                        shard_batch=False)
logits, rcaches = pre(params, jnp.asarray(prompt)[None])
tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
key = jax.random.PRNGKey(0)
tok1_ring, _ = step(params, tok0, rcaches, jnp.int32(9), key)

# Paged: the engine's sharded prefill + one sharded paged decode step.
psv = PagedServeConfig(n_slots=2, page_size=8, n_pages=9, max_len=MAXLEN,
                       cache_dtype=jnp.float32)
eng = PagedEngine(params, ms, psv, mesh=mesh)
rid = eng.add_request(prompt, 2)
eng.step()            # admit + prefill + one decode
toks = eng.request(rid).out
match = (int(tok0[0]) == toks[0]) and (int(tok1_ring[0]) == toks[1])
print("RESULT " + json.dumps({"match": bool(match),
                              "toks": [int(t) for t in toks[:2]]}))
""")
    res = json.loads([l for l in out.splitlines()
                      if l.startswith("RESULT")][0][7:])
    assert res["match"], res


@pytest.mark.slow
def test_pallas_head_selection_matches_xla_under_replicated_kv():
    """tp=4 > n_kv=2 (replicated kv): the Pallas paged kernels' in-kernel
    head_map selection must produce the same streams as the XLA gather
    path, which itself must match the tp=1 engine."""
    out = run_multidevice(r"""
import dataclasses, jax, jax.numpy as jnp, numpy as np, json
from repro.configs import get_config, reduced_config
from repro.core.lp import LPPlan, plan_range
from repro.model import attention as A
from repro.model import transformer as T
from repro.serve import PagedEngine, PagedServeConfig

cfg = reduced_config(get_config("tinyllama-1.1b"), n_layers=4)
cfg = dataclasses.replace(cfg, n_kv_heads=2)
plan = LPPlan(plan_range(cfg, 0, 4).pairs[:2])
ms4 = T.build_structure(cfg, plan=plan, tp=4)
ms1 = T.build_structure(cfg, plan=plan, tp=1)
params = T.init_params(ms1, jax.random.PRNGKey(0))
mesh = jax.make_mesh((1, 4), ("data", "model"))
psv = PagedServeConfig(n_slots=4, page_size=8, n_pages=17, max_len=32,
                       cache_dtype=jnp.float32)
key = jax.random.PRNGKey(3)
prompts = [np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                         (L,), 0, cfg.vocab_size))
           for i, L in enumerate([6, 9, 12])]
outs = {}
for impl, ms, mk in (("xla", ms4, mesh), ("pallas", ms4, mesh),
                     ("tp1", ms1, None)):
    A.set_decode_impl("pallas" if impl == "pallas" else "xla")
    try:
        eng = PagedEngine(params, ms, psv, mesh=mk)
        rids = [eng.add_request(p, 8) for p in prompts]
        eng.drain()
        outs[impl] = [eng.results[r].tolist() for r in rids]
    finally:
        A.set_decode_impl("xla")
print("RESULT " + json.dumps({"px": outs["pallas"] == outs["xla"],
                              "x1": outs["xla"] == outs["tp1"]}))
""")
    res = json.loads([l for l in out.splitlines()
                      if l.startswith("RESULT")][0][7:])
    assert res == {"px": True, "x1": True}, res
