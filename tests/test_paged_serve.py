"""Continuous-batching serve subsystem: paged pair-KV cache + scheduler.

Core invariant: continuous-batched decode — requests admitted at different
steps, mixed prompt lengths, slots and pages recycled mid-flight — produces
exactly the same tokens per request as one-shot ``generate()``. Plus:
paged-vs-ring attention parity at the unit level, the paged Pallas kernel
vs the XLA gather core, page exhaustion -> queuing (no OOM, no
corruption), scheduler/page-pool unit behaviour, and the paged layout
validation gates.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core.lp import LPPlan, plan_range
from repro.model import attention as A
from repro.model import transformer as T
from repro.model.params import init_tree, stack_tmpl
from repro.parallel.context import ParallelContext
from repro.serve import (PagedEngine, PagedServeConfig, PagePool, Scheduler,
                         ServeConfig, generate)
from repro.serve import paged_cache as PG

from _helpers import tiny

PC = ParallelContext()
KEY = jax.random.PRNGKey(0)


def _paginate(cache, page_size):
    """Ring cache [2, B, L, H, hd] -> (pool [2, n_pages, H, ps, hd], block
    tables [B, L/ps]): slot b's pages are contiguous, after a garbage page."""
    P2, B, L, H, hd = cache.shape
    n_pg = L // page_size
    pool = jnp.concatenate(
        [jnp.zeros((P2, 1, H, page_size, hd), cache.dtype),   # garbage page 0
         A.seq_to_pages(cache.reshape(P2, B * L, H, hd), 1, page_size)],
        axis=1)
    bt = 1 + jnp.arange(B * n_pg, dtype=jnp.int32).reshape(B, n_pg)
    return pool, bt


# ---------------------------------------------------------------------------
# Unit parity: paged attention == ring attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pair", [True, False])
def test_paged_decode_matches_ring(pair):
    cfg = tiny(n_layers=2)
    dims = A.attn_dims(cfg, 1)
    tmpl = A.attn_template(cfg, 1)
    p = init_tree(stack_tmpl(tmpl, 2) if pair else tmpl, KEY)
    Bt, L, ps = 2, 32, 8
    t = jnp.array([13, 5], jnp.int32)          # per-slot positions
    shape = (2, Bt, 1, cfg.d_model) if pair else (Bt, 1, cfg.d_model)
    xn = jax.random.normal(jax.random.fold_in(KEY, 1), shape)
    ck = jax.random.normal(jax.random.fold_in(KEY, 2),
                           (2, Bt, L, dims.hkv, dims.hd))
    cv = jax.random.normal(jax.random.fold_in(KEY, 3), ck.shape)
    kp, bt = _paginate(ck, ps)
    vp, _ = _paginate(cv, ps)
    if not pair:
        kp, vp = kp[0], vp[0]

    o_p, nk_p, nv_p = A.decode_attn_paged(
        p, xn, kp, vp, t, bt, cfg, dims, PC, kind="attn", pair=pair)

    # Ring reference: decode_attn_standard takes ONE position for the whole
    # batch, so run it per slot at that slot's position.
    for b in range(Bt):
        sl = (slice(None), slice(b, b + 1)) if pair else slice(b, b + 1)
        o_r, nk_r, nv_r = A.decode_attn_standard(
            p, xn[sl], ck[:, b:b + 1] if pair else ck[0, b:b + 1],
            cv[:, b:b + 1] if pair else cv[0, b:b + 1],
            int(t[b]), cfg, dims, PC, kind="attn", pair=pair)
        assert jnp.allclose(o_p[b:b + 1], o_r, atol=1e-5), b
        # The written slot must land at (bt[b, t//ps], t%ps) in the pool.
        pg, off = int(bt[b, int(t[b]) // ps]), int(t[b]) % ps
        if pair:
            written = nk_p[:, pg, :, off]
            expect = nk_r[:, 0, int(t[b])]
        else:
            written = nk_p[pg, :, off]
            expect = nk_r[0, int(t[b])]
        assert jnp.allclose(written, expect), b


def test_paged_pallas_matches_paged_xla():
    """decode_attention_pair_paged (one launch, block-table index maps)
    == the XLA gather core."""
    cfg = tiny(n_layers=2)
    dims = A.attn_dims(cfg, 1)
    p = init_tree(stack_tmpl(A.attn_template(cfg, 1), 2), KEY)
    Bt, L, ps = 3, 24, 8
    t = jnp.array([17, 3, 10], jnp.int32)
    xn = jax.random.normal(jax.random.fold_in(KEY, 4), (2, Bt, 1, cfg.d_model))
    ck = jax.random.normal(jax.random.fold_in(KEY, 5),
                           (2, Bt, L, dims.hkv, dims.hd))
    cv = jax.random.normal(jax.random.fold_in(KEY, 6), ck.shape)
    kp, bt = _paginate(ck, ps)
    vp, _ = _paginate(cv, ps)
    o_x, nk_x, _ = A.decode_attn_paged(p, xn, kp, vp, t, bt, cfg, dims, PC,
                                       kind="attn", pair=True)
    prev = A.get_decode_impl()
    A.set_decode_impl("pallas")
    try:
        o_p, nk_p, _ = A.decode_attn_paged(p, xn, kp, vp, t, bt, cfg, dims,
                                           PC, kind="attn", pair=True)
    finally:
        A.set_decode_impl(prev)
    assert jnp.allclose(o_p, o_x, atol=2e-5, rtol=2e-5), \
        float(jnp.abs(o_p - o_x).max())
    assert jnp.allclose(nk_p, nk_x)


# ---------------------------------------------------------------------------
# Pool layout
# ---------------------------------------------------------------------------

def test_paged_pool_keeps_stacked_pair_layout():
    cfg = tiny(n_layers=4)
    ms = T.build_structure(cfg, plan=plan_range(cfg, 0, 4), tp=1)
    abs_, _ = PG.paged_cache_meta(ms, n_slots=2, n_pages=9, page_size=8,
                                  dtype=jnp.float32)
    dims = ms.dims
    for seg in abs_:
        assert set(seg.keys()) == {"k", "v"}
        # [count, 2, n_pages, Hkv, page_size, hd] — pair axis INSIDE,
        # head-major pages replace the [B, L] prefix.
        assert seg["k"].shape[1:] == (2, 9, dims.hkv_global, 8, dims.hd)

    ms0 = T.build_structure(cfg, plan=LPPlan(()), tp=1)
    abs0, _ = PG.paged_cache_meta(ms0, n_slots=2, n_pages=9, page_size=8,
                                  dtype=jnp.float32)
    for seg in abs0:
        assert set(seg.keys()) == {"k0", "v0"}
        assert seg["k0"].shape[1:] == (9, dims.hkv_global, 8, dims.hd)


@pytest.mark.parametrize("arch", ["recurrentgemma-9b", "whisper-medium",
                                  "paligemma-3b"])
def test_validate_paged_support_rejects(arch):
    """Window rings, cross-attention, and prefix-LM are not pageable."""
    cfg = reduced_config(get_config(arch), n_layers=4)
    ms = T.build_structure(cfg, tp=1)
    with pytest.raises(ValueError):
        PG.validate_paged_support(ms, 64)


# ---------------------------------------------------------------------------
# The core invariant: continuous batching == one-shot generate()
# ---------------------------------------------------------------------------

def _one_shot(params, ms, prompt, n_new, max_len):
    sv = ServeConfig(max_len=max_len, temperature=0.0,
                     cache_dtype=jnp.float32)
    return np.asarray(generate(params, jnp.asarray(prompt)[None], n_new,
                               ms=ms, pc=PC, sv=sv)[0])


@pytest.mark.parametrize("arch,pallas", [
    ("tinyllama-1.1b", False),
    ("tinyllama-1.1b", True),
    ("falcon-mamba-7b", False),
])
def test_continuous_batching_matches_one_shot(arch, pallas):
    """>= 8 concurrent requests, staggered admission, mixed prompt lengths:
    per-request tokens are EXACTLY those of one-shot generate()."""
    cfg = reduced_config(get_config(arch), n_layers=4)
    plan = plan_range(cfg, 0, 4)
    ms = T.build_structure(cfg, plan=plan, tp=1)
    params = T.init_params(ms, KEY)
    psv = PagedServeConfig(n_slots=8, page_size=8, n_pages=41, max_len=32,
                           cache_dtype=jnp.float32)
    eng = PagedEngine(params, ms, psv)
    lens = [6, 8, 12, 8, 6, 12, 8, 6, 12, 8]
    prompts = [np.asarray(jax.random.randint(jax.random.fold_in(KEY, i),
                                             (L,), 0, cfg.vocab_size))
               for i, L in enumerate(lens)]
    prev = A.get_decode_impl()
    if pallas:
        A.set_decode_impl("pallas")
    try:
        rids = [eng.add_request(p, 5) for p in prompts[:8]]
        s0 = eng.step()
        assert s0["decoded"] == 8, "8 requests must decode concurrently"
        eng.step()
        rids += [eng.add_request(p, 5) for p in prompts[8:]]  # staggered
        res = eng.drain()
    finally:
        A.set_decode_impl(prev)
    for rid, p in zip(rids, prompts):
        ref = _one_shot(params, ms, p, 5, psv.max_len)
        assert (res[rid] == ref).all(), (arch, rid, res[rid], ref)
    assert eng.pool.live == 0
    assert eng.pool.allocated_total == eng.pool.freed_total > 0


def test_page_exhaustion_queues_then_recycles():
    """With pages for only 2 requests in flight, later arrivals QUEUE (no
    OOM), get admitted as pages recycle, and still match one-shot."""
    cfg = tiny(n_layers=4)
    ms = T.build_structure(cfg, plan=plan_range(cfg, 0, 4), tp=1)
    params = T.init_params(ms, KEY)
    # 4 slots but only 4 allocatable pages; each request needs 2 pages.
    psv = PagedServeConfig(n_slots=4, page_size=8, n_pages=5, max_len=16,
                           cache_dtype=jnp.float32)
    eng = PagedEngine(params, ms, psv)
    prompts = [np.asarray(jax.random.randint(jax.random.fold_in(KEY, 40 + i),
                                             (8,), 0, cfg.vocab_size))
               for i in range(5)]
    rids = [eng.add_request(p, 4) for p in prompts]
    s0 = eng.step()
    assert s0["admitted"] == 2 and eng.sched.n_queued == 3  # exhaustion
    assert eng.pool.n_free == 0
    saw_queue_drain = False
    while eng.sched.n_queued or eng.sched.n_running:
        s = eng.step()
        saw_queue_drain = saw_queue_drain or s["admitted"] > 0
    assert saw_queue_drain
    for rid, p in zip(rids, prompts):
        ref = _one_shot(params, ms, p, 4, psv.max_len)
        assert (eng.results[rid] == ref).all(), rid
    assert eng.pool.live == 0
    assert eng.pool.allocated_total == eng.pool.freed_total == 10  # 5 x 2


def test_request_too_large_rejected_up_front():
    cfg = tiny(n_layers=2)
    ms = T.build_structure(cfg, tp=1)
    params = T.init_params(ms, KEY)
    psv = PagedServeConfig(n_slots=2, page_size=8, n_pages=3, max_len=16,
                           cache_dtype=jnp.float32)
    eng = PagedEngine(params, ms, psv)
    # 10 + 7 = 17 positions -> 3 pages > the 2-page pool: can never run.
    with pytest.raises(ValueError):
        eng.add_request(np.zeros(10, np.int32), 7)
    # 2 pages == pool capacity: queues fine.
    eng.add_request(np.zeros(10, np.int32), 6)
    res = eng.drain()
    assert len(res[0]) == 6 and eng.pool.live == 0


# ---------------------------------------------------------------------------
# Scheduler / page-pool units
# ---------------------------------------------------------------------------

def test_page_pool_accounting():
    pool = PagePool(6)           # 5 allocatable + garbage
    a = pool.alloc(3)
    assert a is not None and PG.GARBAGE_PAGE not in a
    assert pool.alloc(3) is None          # exhaustion -> None, not OOM
    b = pool.alloc(2)
    assert pool.live == 5 and pool.n_free == 0
    pool.free(a)
    assert pool.live == 2
    assert pool.allocated_total == 5 and pool.freed_total == 3
    pool.check_balance()
    pool.free(b)
    assert pool.live == 0
    pool.check_balance()


def test_scheduler_fcfs_and_budget():
    pool = PagePool(9)           # 8 allocatable
    sched = Scheduler(n_slots=2, pool=pool, page_size=8, max_len=32,
                      prefill_token_budget=10)
    r0 = sched.submit(np.zeros(8, np.int32), 4)
    r1 = sched.submit(np.zeros(8, np.int32), 4)
    r2 = sched.submit(np.zeros(8, np.int32), 4)
    adm = sched.admit()
    # Budget 10 < 16: only the head admits this step (first ignores budget);
    # slots then cap the next admission wave.
    assert [r.rid for r in adm] == [r0.rid]
    adm = sched.admit()
    assert [r.rid for r in adm] == [r1.rid]
    assert sched.admit() == []            # no free slot -> r2 waits (FCFS)
    sched.finish(r0)
    adm = sched.admit()
    assert [r.rid for r in adm] == [r2.rid]
    assert pool.live == 4
    sched.finish(r1)
    sched.finish(r2)
    assert pool.live == 0
    pool.check_balance()
