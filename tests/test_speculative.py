"""Self-speculative decoding: shallow-Δ drafts, full-depth verify.

Host-side math units (acceptance, packing masks, rewind bookkeeping),
the paged-cache rewind primitives, and the engine-level contract: a
spec_k>0 engine's greedy streams are BIT-IDENTICAL to the plain engine
under staggered continuous batching — in the rejection-heavy regime
(raw random weights: the shallow draft agrees with full depth only at
chance level) and in the trained-model agreement regime (segments
scaled down, where acceptance must actually pay) — and with the radix
prefix cache live, where a hit row prefills only its suffix yet the
drafter must still see the full prompt. Plus the guard rails:
recurrent-state architectures auto-disable speculation with a warning,
and invalid spec configurations raise at construction.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core.lp import LPPlan, plan_range
from repro.model import transformer as T
from repro.serve import (PagedEngine, PagedServeConfig, PagePool,
                         accept_length, build_draft_step, build_trace,
                         build_verify_batch, commit_tokens, draft_plan_for,
                         rewind_plan, rewind_tokens, spec_eligible,
                         stale_span, validate_trace)
from repro.serve import paged_cache as PG

from _helpers import tiny

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Host-side units: plans, masks, acceptance
# ---------------------------------------------------------------------------

def test_draft_plan_must_be_strictly_more_aggressive():
    cfg = tiny(n_layers=4)
    base = plan_range(cfg, 0, 4)            # fully paired already
    with pytest.raises(ValueError, match="strictly more aggressive"):
        draft_plan_for(cfg, base, 0)
    # From an unpaired base, Δ=0 gives the maximal pairing.
    plan = draft_plan_for(cfg, LPPlan(()), 0)
    assert len(plan.pairs) == 2
    # spec_delta > 0 routes through plan_for_depth.
    plan3 = draft_plan_for(cfg, LPPlan(()), 3)
    assert 0 < len(plan3.pairs) <= 2


def test_spec_eligibility_by_mixer():
    cfg = tiny(n_layers=4)
    ms = T.build_structure(cfg, plan=LPPlan(()), tp=1)
    assert spec_eligible(ms)
    cfg_m = reduced_config(get_config("falcon-mamba-7b"), n_layers=2)
    ms_m = T.build_structure(cfg_m, plan=LPPlan(()), tp=1)
    assert not spec_eligible(ms_m)


def test_build_draft_step_masks_idle_and_overflow():
    tok = np.array([7, 9, 11], np.int32)
    pos = np.array([4, 6, 8], np.int32)
    bt = np.arange(6, dtype=np.int32).reshape(3, 2) + 1
    drafts = np.array([[20, 21, 22]], np.int32)
    remaining = np.array([3, 0, -1])        # running / last-token / idle
    t0, p0, b0 = build_draft_step(0, tok, drafts, pos, bt, remaining)
    assert list(t0) == [7, 9, 0] and list(p0) == [4, 6, 0]
    assert (b0[2] == PG.GARBAGE_PAGE).all() and (b0[0] == bt[0]).all()
    # Step 1 feeds draft 0; slot 1 (remaining=0) is now past budget.
    t1, p1, b1 = build_draft_step(1, tok, drafts, pos, bt, remaining)
    assert list(t1) == [20, 0, 0] and list(p1) == [5, 0, 0]
    assert (b1[1] == PG.GARBAGE_PAGE).all()


def test_build_verify_batch_row_layout():
    k = 2
    tok = np.array([7, 9], np.int32)
    pos = np.array([4, 6], np.int32)
    bt = np.arange(4, dtype=np.int32).reshape(2, 2) + 1
    poison = np.array([False, True])
    drafts = np.array([[20, 30], [21, 31]], np.int32)
    remaining = np.array([5, 1])
    tok_v, pos_v, bt_v, poison_v = build_verify_batch(
        k, tok, pos, bt, poison, drafts, remaining)
    # Slot 0 rows 0..2: u_0=tok, u_1=draft0, u_2=draft1 at pos 4,5,6.
    assert list(tok_v[:3]) == [7, 20, 21] and list(pos_v[:3]) == [4, 5, 6]
    # Slot 1 (remaining=1): row j=2 is past budget -> idle convention.
    assert list(tok_v[3:]) == [9, 30, 0] and list(pos_v[3:]) == [6, 7, 0]
    assert (bt_v[5] == PG.GARBAGE_PAGE).all() and (bt_v[4] == bt[1]).all()
    # Poison replicates to the slot's ACTIVE rows only.
    assert list(poison_v) == [False, False, False, True, True, False]


def test_accept_commit_stale_math():
    drafts = np.array([5, 6, 7], np.int32)
    verify = np.array([5, 6, 9, 4], np.int32)   # disagrees at draft 2
    assert accept_length(drafts, verify, 3) == 2
    assert accept_length(drafts, verify, 1) == 1     # cap binds
    assert commit_tokens(drafts, verify, 2) == [5, 6, 9]
    # Bonus-only episode: nothing accepted, full model's own pick.
    assert commit_tokens(drafts, verify, 0) == [5]
    # After accepting a of k probed at p0: [p0+a+1, p0+j_hi+1) is stale.
    assert stale_span(10, 2, 3) == (13, 14)
    assert stale_span(10, 3, 3) == (14, 14)          # full accept: empty


# ---------------------------------------------------------------------------
# Rewind bookkeeping: plan, pool, device zeroing
# ---------------------------------------------------------------------------

def test_rewind_plan_math_and_guards():
    pages, ps = [4, 9, 2], 4
    zero, free = rewind_plan(pages, 0, 5, 10, ps)
    assert zero == [(9, 1), (9, 2), (9, 3), (2, 0), (2, 1)]
    assert free == [2]                  # page 2 holds no live position
    zero, free = rewind_plan(pages, 0, 8, 9, ps)
    assert zero == [(2, 0)] and free == [2]
    assert rewind_plan(pages, 0, 7, 7, ps) == ([], [])
    with pytest.raises(ValueError, match="within the written"):
        rewind_plan(pages, 0, 8, 6, ps)
    with pytest.raises(ValueError, match="read-only"):
        rewind_plan(pages, 1, 3, 10, ps)     # cuts into shared page 0
    with pytest.raises(ValueError, match="exceeds"):
        rewind_plan(pages, 0, 5, 13, ps)


def test_free_rewound_refuses_shared_pages():
    pool = PagePool(n_pages=8)
    own = pool.alloc(2)
    shared = pool.alloc(1)
    pool.share(shared)                       # refcount 2: radix + request
    pool.free_rewound(own)                   # privately held: fine
    with pytest.raises(Exception, match="rewind-free"):
        pool.free_rewound(shared)
    pool.free(shared)
    pool.free(shared)
    pool.check_balance()
    assert pool.live == 0


def test_rewind_tokens_zeroes_only_targeted_positions():
    cfg = tiny(n_layers=2)
    ms = T.build_structure(cfg, plan=plan_range(cfg, 0, 2), tp=1)
    caches = PG.init_paged_caches(ms, n_slots=2, n_pages=5, page_size=4,
                                  dtype=jnp.float32)
    ones = [{n: jnp.ones_like(v) for n, v in seg.items()}
            for seg in caches]
    out = rewind_tokens(ones, jnp.array([2, 3], jnp.int32),
                        jnp.array([1, 0], jnp.int32))
    for seg in out:
        for name, v in seg.items():
            if not PG.is_paged_entry(name):
                assert (np.asarray(v) == 1).all()
                continue
            a = np.asarray(v)
            ba = T.cache_batch_axis(name)
            # [page, offset, ...]: the pool is [.., n_pages, H, ps, hd].
            moved = np.moveaxis(a, (ba, ba + 2), (0, 1))
            assert (moved[2, 1] == 0).all() and (moved[3, 0] == 0).all()
            assert (moved[2, 0] == 1).all() and (moved[1] == 1).all()


# ---------------------------------------------------------------------------
# Engine: bit-identity, counters, telemetry, guards
# ---------------------------------------------------------------------------

def _spec_engines(params, ms, spec_k, **kw):
    psv0 = PagedServeConfig(n_slots=4, page_size=8, n_pages=33, max_len=32,
                            cache_dtype=jnp.float32, **kw)
    psvk = PagedServeConfig(n_slots=4, page_size=8, n_pages=33, max_len=32,
                            cache_dtype=jnp.float32, spec_k=spec_k, **kw)
    return PagedEngine(params, ms, psv0), PagedEngine(params, ms, psvk)


def _staggered_drive(eng, prompts, max_new=7):
    rids = [eng.add_request(p, max_new) for p in prompts[:4]]
    eng.step()
    rids += [eng.add_request(p, max_new) for p in prompts[4:]]
    eng.drain()
    return rids


def _prompts(cfg, lens=(6, 8, 12, 8, 6, 12)):
    return [np.asarray(jax.random.randint(jax.random.fold_in(KEY, i),
                                          (L,), 0, cfg.vocab_size))
            for i, L in enumerate(lens)]


def test_spec_engine_bit_identical_raw_weights():
    """Raw random weights: chance-level draft agreement — the rejection
    and rewind paths run hot, and the stream may not move a bit."""
    cfg = tiny(n_layers=4)
    ms = T.build_structure(cfg, plan=LPPlan(()), tp=1)
    params = T.init_params(ms, KEY)
    eng0, engk = _spec_engines(params, ms, spec_k=2)
    prompts = _prompts(cfg)
    rids0 = _staggered_drive(eng0, prompts)
    ridsk = _staggered_drive(engk, prompts)
    for r0, rk in zip(rids0, ridsk):
        assert (eng0.results[r0] == engk.results[rk]).all(), (r0, rk)
    c = engk.counters
    assert c["verify_steps"] > 0
    assert c["draft_steps"] == 2 * c["verify_steps"]
    assert c["spec_accepted"] + c["spec_rejected"] > 0
    assert c["spec_rejected"] > 0            # raw weights DO reject
    assert c["spec_rewound"] > 0             # ...and rejections rewind
    assert engk.pool.live == 0
    assert engk.pool.allocated_total == engk.pool.freed_total > 0
    # Episode telemetry: one histogram observation + one spec_log row
    # per running slot per verify; the trace renders them as slices.
    h = engk.telemetry.hists["spec_accept"]
    assert h.count == len(engk.telemetry.spec_log) > 0
    doc = build_trace(engk.telemetry, n_slots=4)
    validate_trace(doc)
    spec_slices = [e for e in doc["traceEvents"] if e.get("cat") == "spec"]
    assert len(spec_slices) == len(engk.telemetry.spec_log)
    assert all(e["name"].startswith("spec:") for e in spec_slices)


def test_spec_engine_accepts_in_agreement_regime():
    """Segments scaled toward identity: the shallow draft agrees with
    full depth (the trained-model regime) — still bit-identical, and
    acceptance must beat one token per verify."""
    cfg = tiny(n_layers=4)
    ms = T.build_structure(cfg, plan=LPPlan(()), tp=1)
    params = T.init_params(ms, KEY)
    params = dict(params, segments=jax.tree.map(lambda x: x * 0.1,
                                                params["segments"]))
    eng0, engk = _spec_engines(params, ms, spec_k=2)
    prompts = _prompts(cfg)
    rids0 = _staggered_drive(eng0, prompts)
    ridsk = _staggered_drive(engk, prompts)
    for r0, rk in zip(rids0, ridsk):
        assert (eng0.results[r0] == engk.results[rk]).all(), (r0, rk)
    snap = engk.metrics_snapshot()
    spec = snap["spec"]
    assert spec["k"] == 2
    assert spec["draft_eff_depth"] == engk.ms_draft.effective_depth
    assert spec["accept_per_verify"] > 1.0, spec
    assert engk.counters["spec_accepted"] > 0
    # Fewer engine steps than the plain engine: the speedup's
    # deterministic form.
    assert engk.step_count < eng0.step_count


def test_spec_engine_bit_identical_with_prefix_cache():
    """spec_k x radix cache: a prefix-HIT member rides the bucketed
    suffix path while speculation is live.  The hit row sits out the
    draft mirror's bucket prefill until the engine primes it with a
    full-prompt draft prefill, so drafts see the tokens the shared pages
    hold — gated here by bit-identity to the plain prefix-on engine,
    with BOTH subsystems proven hot by the counters."""
    cfg = tiny(n_layers=4)
    ms = T.build_structure(cfg, plan=LPPlan(()), tp=1)
    params = T.init_params(ms, KEY)
    eng0, engk = _spec_engines(params, ms, spec_k=2, prefix_cache=True)

    def toks(i, L):
        return np.asarray(jax.random.randint(jax.random.fold_in(KEY, 10 + i),
                                             (L,), 0, cfg.vocab_size))

    shared = toks(0, 8)                         # one whole page
    donor = np.concatenate([shared, toks(1, 8)])
    member = np.concatenate([shared, toks(2, 6)])
    cold = toks(3, 7)
    rids = []
    for eng in (eng0, engk):
        r0 = eng.add_request(donor, 5)
        eng.drain()                             # donates the shared page
        r1 = eng.add_request(member, 5)
        r2 = eng.add_request(cold, 5)
        eng.drain()
        rids.append((r0, r1, r2))
    for a, b in zip(*rids):
        assert (eng0.results[a] == engk.results[b]).all(), (a, b)
    for eng in (eng0, engk):
        c = eng.counters
        assert c["prefix_hits"] == 1, dict(c)
        assert c["suffix_prefills"] == 1, dict(c)
        assert eng.pool.live == eng.prefix.resident_pages
    ck = engk.counters
    assert ck["verify_steps"] > 0, dict(ck)
    assert ck["draft_steps"] == 2 * ck["verify_steps"], dict(ck)
    assert ck["spec_accepted"] + ck["spec_rejected"] > 0, dict(ck)


def test_spec_auto_disables_on_recurrent_blocks():
    """State-model guard: mamba blocks have no per-position kv to rewind
    — spec_k must drop to 0 with an actionable warning, and the fallback
    engine must stay bit-identical to a spec_k=0 engine."""
    cfg = reduced_config(get_config("falcon-mamba-7b"), n_layers=4)
    ms = T.build_structure(cfg, plan=plan_range(cfg, 0, 4), tp=1)
    params = T.init_params(ms, KEY)
    psv = PagedServeConfig(n_slots=4, page_size=8, n_pages=33, max_len=32,
                           cache_dtype=jnp.float32, spec_k=2)
    with pytest.warns(UserWarning, match="auto-disabled"):
        engk = PagedEngine(params, ms, psv)
    assert engk.spec_k == 0 and engk.ms_draft is None
    psv0 = PagedServeConfig(n_slots=4, page_size=8, n_pages=33, max_len=32,
                            cache_dtype=jnp.float32)
    eng0 = PagedEngine(params, ms, psv0)
    prompts = _prompts(cfg, lens=(6, 8, 12))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        rk = [engk.add_request(p, 5) for p in prompts]
        engk.drain()
    r0 = [eng0.add_request(p, 5) for p in prompts]
    eng0.drain()
    for a, b in zip(rk, r0):
        assert (engk.results[a] == eng0.results[b]).all()
    assert "spec" not in engk.metrics_snapshot()


def test_spec_config_validation():
    cfg = tiny(n_layers=4)
    ms = T.build_structure(cfg, plan=LPPlan(()), tp=1)
    params = T.init_params(ms, KEY)

    def psv(**kw):
        return PagedServeConfig(n_slots=4, page_size=8, n_pages=33,
                                max_len=32, cache_dtype=jnp.float32, **kw)

    with pytest.raises(ValueError, match="spec_k"):
        PagedEngine(params, ms, psv(spec_k=-1))
    with pytest.raises(ValueError, match="greedy"):
        PagedEngine(params, ms, psv(spec_k=2, temperature=0.7))
    with pytest.raises(ValueError, match="degrade"):
        PagedEngine(params, ms, psv(spec_k=2, degrade_delta=True,
                                    degrade_slots=2))
    with pytest.raises(ValueError, match="spec_delta"):
        PagedEngine(params, ms, psv(spec_delta=3))
    # Base already maximally paired: no strictly-more-aggressive draft.
    ms_full = T.build_structure(cfg, plan=plan_range(cfg, 0, 4), tp=1)
    params_full = T.init_params(ms_full, KEY)
    with pytest.raises(ValueError, match="aggressive"):
        PagedEngine(params_full, ms_full, psv(spec_k=2))
