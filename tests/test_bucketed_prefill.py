"""Bucketed batched prefill: bit-identity, padding containment, config.

The tentpole contract: an engine that right-pads admitted prompts to a
bucket ladder and prefills several requests in ONE launch must produce
per-request greedy streams BIT-identical to the exact-length engine (and
so to one-shot ``generate()``), while bounding prefill compile count by
the ladder length. Padding must be contained: pad rows and pad pages
write nothing into the pool, and the radix tree never sees a padded
page. The config redesign rides along: grouped sub-configs are pure
views over the flat fields, and ``validate()`` is the one entry point
for every cross-field rule.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.model import transformer as T
from repro.parallel.context import ParallelContext
from repro.serve import (AdmissionConfig, DegradeConfig, PagedEngine,
                         PagedServeConfig, PagePool, ProgramCache,
                         Scheduler, ServeConfig, SpecConfig, Telemetry,
                         TelemetryConfig, bucket_for, default_buckets,
                         generate, make_paged_bucket_prefill_fn,
                         rows_for_bucket, validate_buckets)
from repro.serve import paged_cache as PG
from repro.serve.engine import (make_paged_prefill_fn,
                                make_paged_suffix_prefill_fn)

from _helpers import tiny

KEY = jax.random.PRNGKey(0)
PC = ParallelContext()


def _build(n_layers=2):
    cfg = tiny(n_layers=n_layers)
    ms = T.build_structure(cfg, tp=1)
    return cfg, ms, T.init_params(ms, KEY)


def _psv(**kw):
    base = dict(n_slots=4, page_size=8, n_pages=21, max_len=32,
                cache_dtype=jnp.float32)
    base.update(kw)
    return PagedServeConfig(**base)


def _prompt(i, length, vocab):
    return np.asarray(jax.random.randint(jax.random.fold_in(KEY, i),
                                         (length,), 0, vocab))


# ---------------------------------------------------------------------------
# Ladder math
# ---------------------------------------------------------------------------

def test_ladder_math():
    assert default_buckets(48, 8) == (8, 16, 32, 48)
    assert default_buckets(32, 8) == (8, 16, 32)
    assert bucket_for(5, (8, 16)) == 8
    assert bucket_for(9, (8, 16)) == 16
    assert bucket_for(17, (8, 16)) is None
    assert rows_for_bucket(8, 4, 4096) == 4     # slot-capped
    assert rows_for_bucket(16, 8, 32) == 2      # budget-capped
    assert rows_for_bucket(64, 8, 32) == 1      # floor: wider than budget
    validate_buckets((8, 16, 32), page_size=8, max_len=32)
    with pytest.raises(ValueError, match="strictly increasing"):
        validate_buckets((16, 8), page_size=8, max_len=32)
    with pytest.raises(ValueError, match="multiple of"):
        validate_buckets((8, 12), page_size=8, max_len=32)
    with pytest.raises(ValueError, match="exceeds max_len"):
        validate_buckets((8, 64), page_size=8, max_len=32)


# ---------------------------------------------------------------------------
# The tentpole bit-identity contract
# ---------------------------------------------------------------------------

def test_bucketed_engine_matches_exact_engine_staggered():
    """Staggered arrivals, mixed lengths: the bucketed engine's streams
    are BIT-identical to the exact-length reference engine's
    (``prefill_buckets=()``), and page accounting balances in both."""
    cfg, ms, params = _build()
    lens = [5, 8, 12, 16, 7, 20, 9, 13]
    prompts = [_prompt(i, L, cfg.vocab_size) for i, L in enumerate(lens)]
    engines = [PagedEngine(params, ms, _psv(prefill_buckets=pb))
               for pb in (None, ())]
    assert engines[0]._buckets == (8, 16, 32)
    assert engines[1]._buckets == ()
    for eng in engines:
        for p in prompts[:5]:
            eng.add_request(p, 6)
        for _ in range(2):
            eng.step()
        for p in prompts[5:]:
            eng.add_request(p, 6)
        eng.drain()
        eng.pool.check_balance()
        assert eng.pool.live == 0
    bkt, ref = engines
    assert sorted(bkt.results) == sorted(ref.results)
    for rid in bkt.results:
        assert (bkt.results[rid] == ref.results[rid]).all(), rid
    assert bkt.counters["bucket_prefills"] == len(lens)
    assert bkt.counters["bucket_groups"] >= 1
    assert bkt.counters["pad_tokens"] > 0
    assert ref.counters["bucket_prefills"] == 0
    # Compile count bounded by the ladder, not by the 7 distinct lengths.
    bkt_pins = [k for k in bkt.telemetry.compiles if k[1] == "prefill_bucket"]
    assert 1 <= len(bkt_pins) <= len(bkt._buckets)


def test_bucketed_engine_matches_one_shot_generate():
    cfg, ms, params = _build(n_layers=4)
    eng = PagedEngine(params, ms, _psv())
    lens = [6, 11, 8, 14]
    prompts = [_prompt(i, L, cfg.vocab_size) for i, L in enumerate(lens)]
    rids = [eng.add_request(p, 5) for p in prompts]
    eng.drain()
    sv = ServeConfig(max_len=32, temperature=0.0, cache_dtype=jnp.float32)
    for rid, p in zip(rids, prompts):
        ref = np.asarray(generate(params, jnp.asarray(p)[None], 5,
                                  ms=ms, pc=PC, sv=sv)[0])
        assert (eng.results[rid] == ref).all(), rid


def test_bucket_fn_matches_exact_fn_rowwise():
    """Program level: one [rows, bucket] launch with right-padded prompts
    and an inert pad row produces, per real row, the SAME first token and
    the same page kv as the exact-length batch-1 program, to f32 rounding.

    Bit equality is not the contract here: XLA:CPU picks the blocking of
    the MLP's d_ff-wide matmuls (up and down projections) by the operand's
    row count, so the kv of every layer after the first can differ between
    a [rows, bucket] launch and a [1, L] launch in the last f32 bits (~1e-6
    on O(1) values). Layer 0's kv, which no MLP feeds, still matches bit
    for bit."""
    cfg, ms, params = _build()
    psv = _psv()
    ps = psv.page_size
    bucket, rows = 16, 3
    lens = [9, 16]
    prompts_np = [_prompt(i, L, cfg.vocab_size) for i, L in enumerate(lens)]
    key = jax.random.PRNGKey(7)

    fn_b = jax.jit(make_paged_bucket_prefill_fn(ms, PC, psv, bucket, rows))
    n_pg = bucket // ps
    prompts = np.zeros((rows, bucket), np.int32)
    true_lens = np.ones((rows,), np.int32)
    page_ids = np.full((rows, n_pg), PG.GARBAGE_PAGE, np.int32)
    pages = [[1, 2], [3, 4]]       # rows 0..1 real, row 2 inert pad
    for i, (p, L) in enumerate(zip(prompts_np, lens)):
        prompts[i, :L] = p
        true_lens[i] = L
        page_ids[i, :-(-L // ps)] = pages[i][:-(-L // ps)]
    caches = PG.init_paged_caches(ms, n_slots=psv.n_slots,
                                  n_pages=psv.n_pages, page_size=ps,
                                  dtype=psv.cache_dtype)
    tok_b, ok_b, caches_b = fn_b(params, caches,
                                 jnp.asarray(prompts),
                                 jnp.asarray(true_lens),
                                 jnp.asarray(page_ids), key)
    assert np.asarray(ok_b).all()
    for i, (p, L) in enumerate(zip(prompts_np, lens)):
        fn_e = jax.jit(make_paged_prefill_fn(ms, PC, psv, L))
        caches_e = PG.init_paged_caches(ms, n_slots=psv.n_slots,
                                        n_pages=psv.n_pages, page_size=ps,
                                        dtype=psv.cache_dtype)
        npg = -(-L // ps)
        tok_e, _, caches_e = fn_e(params, caches_e,
                                  jnp.asarray(p[None]),
                                  jnp.asarray(pages[i][:npg], jnp.int32),
                                  jnp.int32(i), key)
        assert int(np.asarray(tok_b)[i]) == int(np.asarray(tok_e)[0])
        for seg_b, seg_e in zip(caches_b, caches_e):
            for name in seg_b:
                if not PG.is_paged_entry(name):
                    continue
                ba = T.cache_batch_axis(name)
                for pg in pages[i][:npg]:
                    # Agreement over the page's REAL positions (the pool
                    # is [.., page, head, position, hd]); the tail of a
                    # partial page holds junk kv in the bucketed tree but
                    # is never unmasked before decode overwrites it.
                    n_real = min(ps, L - pages[i].index(pg) * ps)
                    sl = ((slice(None),) * ba
                          + (pg, slice(None), slice(0, n_real)))
                    got = np.asarray(seg_b[name][sl])
                    want = np.asarray(seg_e[name][sl])
                    assert (got[0] == want[0]).all(), name     # layer 0
                    np.testing.assert_allclose(got, want, rtol=1e-5,
                                               atol=1e-5, err_msg=name)


def test_bucket_ctx_fn_matches_suffix_and_exact_fn_rowwise():
    """Program level, ctx-AWARE bucket: one [rows, bucket] launch carrying
    a radix-HIT row (per-row ctx-page gather), a COLD row (ctx_len 0,
    all-garbage ctx ids), and an inert pad row. The hit row must match the
    exact-length suffix program bit for bit (token + suffix page kv); the
    cold row must match the exact-length full program — heterogeneous
    (ctx_pages, suffix_len) rows share ONE launch without moving a bit."""
    cfg, ms, params = _build()
    psv = _psv()
    ps = psv.page_size
    key = jax.random.PRNGKey(7)

    # Donor: 16 shared tokens prefilled into pages (1, 2) — the radix ctx.
    donor = _prompt(0, 16, cfg.vocab_size)
    caches = PG.init_paged_caches(ms, n_slots=psv.n_slots,
                                  n_pages=psv.n_pages, page_size=ps,
                                  dtype=psv.cache_dtype)
    fn_d = jax.jit(make_paged_prefill_fn(ms, PC, psv, 16))
    _, _, caches = fn_d(params, caches, jnp.asarray(donor[None]),
                        jnp.asarray([1, 2], jnp.int32), jnp.int32(0), key)

    tail = _prompt(1, 6, cfg.vocab_size)     # hit row: ctx 16 + suffix 6
    cold = _prompt(2, 7, cfg.vocab_size)     # cold row: 7 fresh tokens
    bucket, rows, ctx_pages = 8, 3, 3        # pages_per_slot - 1
    prompts = np.zeros((rows, bucket), np.int32)
    true_lens = np.ones((rows,), np.int32)
    page_ids = np.full((rows, 1), PG.GARBAGE_PAGE, np.int32)
    ctx_ids = np.full((rows, ctx_pages), PG.GARBAGE_PAGE, np.int32)
    ctx_lens = np.zeros((rows,), np.int32)
    prompts[0, :6] = tail
    true_lens[0] = 6
    page_ids[0, 0] = 3
    ctx_ids[0, :2] = (1, 2)
    ctx_lens[0] = 16
    prompts[1, :7] = cold
    true_lens[1] = 7
    page_ids[1, 0] = 4
    fn_b = jax.jit(make_paged_bucket_prefill_fn(ms, PC, psv, bucket, rows,
                                                ctx_pages))
    tok_b, ok_b, caches_b = fn_b(params, caches, jnp.asarray(prompts),
                                 jnp.asarray(true_lens),
                                 jnp.asarray(page_ids),
                                 jnp.asarray(ctx_ids),
                                 jnp.asarray(ctx_lens), key)
    assert np.asarray(ok_b).all()

    # Hit-row reference: the exact-length suffix program over the SAME
    # donor caches.
    fn_s = jax.jit(make_paged_suffix_prefill_fn(ms, PC, psv, 2, 6))
    tok_s, ok_s, caches_s = fn_s(params, caches, jnp.asarray(tail[None]),
                                 jnp.asarray([1, 2], jnp.int32),
                                 jnp.asarray([3], jnp.int32),
                                 jnp.int32(0), key)
    assert np.asarray(ok_s).all()
    assert int(np.asarray(tok_b)[0]) == int(np.asarray(tok_s)[0])

    # Cold-row reference: the exact-length full program on a fresh pool.
    fn_e = jax.jit(make_paged_prefill_fn(ms, PC, psv, 7))
    caches_e = PG.init_paged_caches(ms, n_slots=psv.n_slots,
                                    n_pages=psv.n_pages, page_size=ps,
                                    dtype=psv.cache_dtype)
    tok_e, _, caches_e = fn_e(params, caches_e, jnp.asarray(cold[None]),
                              jnp.asarray([4], jnp.int32), jnp.int32(1), key)
    assert int(np.asarray(tok_b)[1]) == int(np.asarray(tok_e)[0])

    for (pg, n_real, ref) in ((3, 6, caches_s), (4, 7, caches_e)):
        for seg_b, seg_r in zip(caches_b, ref):
            for name in seg_b:
                if not PG.is_paged_entry(name):
                    continue
                ba = T.cache_batch_axis(name)
                sl = ((slice(None),) * ba
                      + (pg, slice(None), slice(0, n_real)))
                got = np.asarray(seg_b[name][sl])
                want = np.asarray(seg_r[name][sl])
                assert (got == want).all(), (name, pg)


def test_engine_hit_and_cold_rows_share_one_bucket_group():
    """Engine level: a radix-HIT member and a COLD request admitted
    together land in the SAME bucket group (one launch), the hit prefills
    only its suffix, prefill compiles stay bounded by the ladder with no
    exact-length program ever built, and all streams are bit-identical to
    one-shot ``generate()``."""
    cfg, ms, params = _build()
    eng = PagedEngine(params, ms, _psv(prefix_cache=True))
    shared = _prompt(0, 8, cfg.vocab_size)          # one whole page
    donor = np.concatenate([shared, _prompt(1, 8, cfg.vocab_size)])
    member = np.concatenate([shared, _prompt(2, 6, cfg.vocab_size)])
    cold = _prompt(3, 7, cfg.vocab_size)
    rid0 = eng.add_request(donor, 5)
    eng.drain()                                     # donates the shared page
    g0 = eng.counters["bucket_groups"]
    assert g0 == 1 and eng.counters["prefix_hits"] == 0
    rid1 = eng.add_request(member, 5)
    rid2 = eng.add_request(cold, 5)
    eng.drain()
    c = eng.counters
    assert c["bucket_groups"] == g0 + 1, dict(c)    # ONE shared launch
    assert c["prefix_hits"] == 1, dict(c)
    assert c["suffix_prefills"] == 1, dict(c)
    assert c["full_prefills"] == 2, dict(c)         # donor + cold
    assert c["bucket_prefills"] == 3, dict(c)
    pins = [k for k in eng.telemetry.compiles if k[1] == "prefill_bucket"]
    assert 1 <= len(pins) <= len(eng._buckets), pins
    assert not any(k[1] in ("prefill_full", "prefill_suffix")
                   for k in eng.telemetry.compiles), (
        dict(eng.telemetry.compiles))
    sv = ServeConfig(max_len=32, temperature=0.0, cache_dtype=jnp.float32)
    for rid, p in ((rid0, donor), (rid1, member), (rid2, cold)):
        ref = np.asarray(generate(params, jnp.asarray(p)[None], 5,
                                  ms=ms, pc=PC, sv=sv)[0])
        assert (eng.results[rid] == ref).all(), rid


def test_scatter_rows_masks_pad_rows_and_pages():
    """Garbage-directed rows/pages write NOTHING: the garbage page stays
    zero and no allocatable page moves."""
    cfg, ms, params = _build()
    psv = _psv()
    caches = PG.init_paged_caches(ms, n_slots=psv.n_slots,
                                  n_pages=psv.n_pages,
                                  page_size=psv.page_size,
                                  dtype=psv.cache_dtype)
    bucket, rows = 16, 2
    fn = jax.jit(make_paged_bucket_prefill_fn(ms, PC, psv, bucket, rows))
    prompts = np.zeros((rows, bucket), np.int32)
    prompts[0, :9] = _prompt(0, 9, cfg.vocab_size)
    true_lens = np.asarray([9, 1], np.int32)
    page_ids = np.full((rows, 2), PG.GARBAGE_PAGE, np.int32)
    page_ids[0] = (5, 6)           # row 1 is ALL pad
    before = jax.tree.map(np.asarray, caches)
    _, _, caches = fn(params, caches, jnp.asarray(prompts),
                      jnp.asarray(true_lens), jnp.asarray(page_ids),
                      jax.random.PRNGKey(0))
    for seg_b, seg_a in zip(before, caches):
        for name in seg_b:
            if not PG.is_paged_entry(name):
                continue
            ba = T.cache_batch_axis(name)
            after = np.asarray(seg_a[name])
            for pg in range(psv.n_pages):
                sl = (slice(None),) * ba + (pg,)
                if pg in (5, 6):
                    continue       # the one real row's pages
                assert (after[sl] == seg_b[name][sl]).all(), (name, pg)
                if pg == PG.GARBAGE_PAGE:
                    assert (after[sl] == 0).all(), name


def test_radix_never_donates_a_padded_page():
    """Donation is structural: ``r.pages`` only ever holds the request's
    ALLOCATED pages (ceil(Lp/ps) of them), so bucket pad pages cannot
    reach the tree — and a same-prefix follower still bit-matches the
    exact engine."""
    cfg, ms, params = _build()
    lens = [12, 12, 5]             # 12 -> bucket 16: one padded page slot
    base = _prompt(0, 12, cfg.vocab_size)
    prompts = [base, base, _prompt(2, 5, cfg.vocab_size)]
    engines = [PagedEngine(params, ms,
                           _psv(prefix_cache=True, prefill_buckets=pb))
               for pb in (None, ())]
    for eng in engines:
        rids = [eng.add_request(p, 4) for p in prompts]
        eng.drain()
        if eng._buckets:
            # Every radix-held page id was allocated for REAL prompt
            # tokens — donation only ever considers len(tokens)//ps WHOLE
            # prompt pages, so a bucket's padded page slots (GARBAGE ids,
            # never allocated) are structurally unreachable.
            held = set()
            stack = list(eng.prefix.root.children.values())
            while stack:
                n = stack.pop()
                held.add(n.page)
                stack.extend(n.children.values())
            assert PG.GARBAGE_PAGE not in held
            assert len(held) <= 2          # 12//8 + 5//8 whole pages
    bkt, ref = engines
    for rid in bkt.results:
        assert (bkt.results[rid] == ref.results[rid]).all(), rid
    assert bkt.counters["prefix_hits"] == ref.counters["prefix_hits"]


# ---------------------------------------------------------------------------
# Scheduler: the budget counts what the device computes
# ---------------------------------------------------------------------------

def test_scheduler_budget_counts_padded_tokens():
    def mk(buckets):
        pool = PagePool(9)
        s = Scheduler(n_slots=4, pool=pool, page_size=8, max_len=32,
                      prefill_token_budget=20, prefill_buckets=buckets)
        for i in range(2):
            s.submit(np.zeros(9, np.int32), 2, -1)
        return s

    exact = mk(())
    assert len(exact.admit(0)) == 2        # 9 + 9 <= 20
    padded = mk((16,))
    # First admission ignores the budget (anti-livelock), but its PADDED
    # cost (16) leaves only 4 — the second 16-wide admission must wait.
    assert len(padded.admit(0)) == 1


# ---------------------------------------------------------------------------
# Config groups + ProgramCache
# ---------------------------------------------------------------------------

def test_config_groups_are_views_over_flats():
    flat = PagedServeConfig(n_slots=4, page_size=8, n_pages=9, max_len=32,
                            prefill_token_budget=64, max_queue=3,
                            degrade_delta=True, degrade_slots=1,
                            degrade_queue_depth=2, degrade_eff_depth=2,
                            telemetry=False, profile_decode=True)
    grouped = PagedServeConfig(
        n_slots=4, page_size=8, n_pages=9, max_len=32,
        admission=AdmissionConfig(prefill_token_budget=64, max_queue=3),
        degrade=DegradeConfig(enabled=True, slots=1, queue_depth=2,
                              eff_depth=2),
        telemetry_cfg=TelemetryConfig(enabled=False, profile_decode=True))
    assert flat == grouped
    assert grouped.degrade_slots == 1 and grouped.max_queue == 3
    assert flat.admission == AdmissionConfig(prefill_token_budget=64,
                                             max_queue=3)
    spec = PagedServeConfig(n_slots=4, page_size=8, n_pages=9, max_len=32,
                            spec=SpecConfig(k=2, delta=3))
    assert spec.spec_k == 2 and spec.spec_delta == 3
    spec.validate()


def test_validate_is_the_single_entry_point():
    def cfg(**kw):
        return _psv(**kw)

    with pytest.raises(ValueError, match="whole number of pages"):
        cfg(max_len=20).validate()
    with pytest.raises(ValueError, match="n_slots=0 must be >= 1"):
        cfg(n_slots=0).validate()
    with pytest.raises(ValueError, match="without spec_k"):
        cfg(spec_delta=3).validate()
    with pytest.raises(ValueError, match="without degrade_delta"):
        cfg(degrade_slots=1).validate()
    with pytest.raises(ValueError, match="tp=1-only"):
        cfg(spec_k=2, spec_delta=3).validate(mesh=True)
    with pytest.raises(ValueError, match="multiple of"):
        cfg(prefill_buckets=(8, 12)).validate()
    # The engine routes through validate(): a bad ladder dies in __init__.
    cfg_bad = cfg(prefill_buckets=(12,))
    _, ms, params = _build()
    with pytest.raises(ValueError, match="multiple of"):
        PagedEngine(params, ms, cfg_bad)


def test_program_cache_single_increment_site():
    tel = Telemetry()
    pc = ProgramCache(tel)
    built = []

    def build():
        built.append(1)
        return "fn"

    assert pc.get("main", "decode", 4, build) == "fn"
    assert pc.get("main", "decode", 4, build) == "fn"
    assert built == [1]                       # one build...
    assert tel.compiles[("main", "decode", 4)] == 1   # ...one event
    assert ("main", "decode", 4) in pc and len(pc) == 1
    pc.note("spec_verify", "decode", 8)       # fused-program second body
    assert tel.compiles[("spec_verify", "decode", 8)] == 1
    assert len(pc) == 1                       # note() caches nothing
