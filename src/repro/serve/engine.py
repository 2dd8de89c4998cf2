"""Serving engine: batched prefill + autoregressive decode with LP models.

The engine exposes the three programs the assigned shapes lower:
  prefill_step  — logits + cache from a full prompt batch   (prefill_32k)
  serve_step    — ONE new token against the cache            (decode_32k /
                  long_500k; this is where LP's sync halving shows up —
                  seq=1 matmuls are tiny, so decode latency on a TP mesh is
                  dominated by the per-layer all-reduces the paper removes)
  generate      — host loop / scanned loop over serve_step

Sampling is vocab-parallel (Gumbel-max over the sharded vocabulary), so full
logits are never gathered.

Continuous batching
-------------------
``PagedEngine`` is the deployment-shaped entry point: requests of different
lengths arrive at different times, share ONE paged pair-KV cache pool
(repro.serve.paged_cache), and finish independently — ``add_request`` /
``step`` / ``drain``. The decode step stays ONE compiled program: the batch
is a fixed set of ``n_slots`` decode slots (idle slots point at the garbage
page and their outputs are ignored on the host), with per-slot positions
and a block table as the only per-step inputs. Prefill compiles per
distinct prompt length and runs the EXACT prompt (no right-padding), which
is what makes engine outputs bit-identical to one-shot ``generate()`` —
padding would change reduction shapes and perturb low bits. Admission is
FCFS with a prefill token budget (repro.serve.scheduler) so prefill bursts
interleave with, rather than starve, running decodes.

Prefix sharing & preemption (PagedServeConfig.prefix_cache/preempt_after):
admission radix-matches the prompt against donated whole pages
(repro.serve.prefix_cache) — matched pages link read-only into the block
table (copy-on-write: the first written page is always private) and only
the unmatched suffix runs through ``_suffix_fn``, a forward over the
suffix with the matched pages gathered as context kv whose rows reduce at
the cold program's exact shapes. A blocked queue head preempts the
youngest running request: its tokens park on the Request, its whole
written pages are donated (reclaimable, radix-hittable at resume), and
resume replays the parked positions through the regular decode program —
the engine asserts every replayed token reproduces the parked one.

Robustness (request lifecycle, fault isolation, chaos, degradation)
-------------------------------------------------------------------
Per-request failures are CONTAINED, never engine-fatal. The decode and
prefill programs return a per-row finite flag alongside tokens (NaN/inf
logits or non-finite emitted cache values), the block table of every
running slot is validated against its request's owned pages before each
launch, and prompts are re-checked against the vocabulary at the device
boundary. A tripped guard FAILs exactly the offending request — its
private pages are scrubbed (zeroed) before returning to the free list so
stale NaN cannot leak to a later holder — while every surviving stream
stays bit-identical to a fault-free run (the chaos CI gate). Requests
carry deadlines (expired at step boundaries) and can be cancelled;
``PagedServeConfig.max_queue`` bounds the submit queue with deadline-aware
shedding. ``fault_plan`` (repro.serve.faults.FaultPlan) injects seeded,
reproducible faults through the same hooks the real failures would take.

``degrade_delta`` turns overload into the paper's retraining-free
depth/quality trade instead of queueing: the engine re-pairs the SAME
weights under a more aggressive Δ plan (repro.core.lp.replan — no reload,
no retraining) and reserves ``degrade_slots`` decode slots as a DEGRADED
cohort running a second precompiled decode program over a separate cache
pool tree. Under SLO pressure (queue depth >= degrade_queue_depth) new
admissions overflow into that cohort; its greedy streams are bit-identical
to an engine built wholly at the aggressive Δ (the overload CI gate), and
cohorts never share radix pages (kv bits are plan-specific).

Sharded paged serving (``PagedEngine(mesh=...)``): the same engine loop
drives shard_map-compiled programs on a tp > 1 mesh. The page pool shards
its kv-head axis over the "model" axis exactly like the ring cache, every
host-side structure (scheduler, block tables, positions, page ids) is
tp-agnostic, and greedy decode streams stay bit-identical to the tp=1
engine and to one-shot ``sharded_generate`` (the sharded-structural CI
gate). Prefix sharing runs under tp > 1 too: the suffix-prefill ctx fold
branches per rank (kv-sharded pool: the gathered ctx arrives rank-local;
replicated pool: the rank in-gathers its head(s) like the paged decode
kernel), so radix hits keep their ~10x TTFT win exactly where production
runs — gated by the sharded-structural shared-prefix job.
"""
from __future__ import annotations

import contextlib
import warnings
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import lp as LP
from repro.model import embedding as E
from repro.model import transformer as T
from repro.parallel.context import ParallelContext, make_context
from repro.serve import bucketing as BK
from repro.serve import faults as F
from repro.serve import paged_cache as PG
from repro.serve import speculative as SP
from repro.serve.faults import (BlockTableCorruptionError,
                                DeadlineExceededError, InvalidRequestError,
                                LoadShedError, NonFiniteLogitsError,
                                PoisonedPromptError, QueueFullError)
from repro.serve.prefix_cache import PrefixCache
from repro.serve.scheduler import (COHORT_DEGRADED, COHORT_MAIN,
                                   TERMINAL_STATES, PagePool, Request,
                                   Scheduler)
from repro.serve.telemetry import (DECODE, PREFILL, REPLAY, ProgramCache,
                                   Telemetry)
from repro.serve.trace import write_trace

PyTree = Any


@dataclass(frozen=True)
class ServeConfig:
    max_len: int = 1024           # KV-cache length
    temperature: float = 0.0      # 0 -> greedy
    kv_mode: str = "heads"        # heads | seq  (seq-sharded KV cache)
    cache_dtype: Any = jnp.bfloat16
    # Pinned-tile chunked attention: the impl whose prefill output is
    # bit-invariant to right-padding the key axis (serve.bucketing). The
    # one-shot reference and the engine's prefills must run the SAME impl
    # or the engine==generate() bit-identity gates would compare different
    # reduction tilings.
    attn_impl: str = BK.PREFILL_ATTN_IMPL


# ---------------------------------------------------------------------------
# Local step functions (run under shard_map or plain)
# ---------------------------------------------------------------------------

def make_prefill(ms: T.ModelStructure, pc: ParallelContext, sv: ServeConfig):
    def prefill_fn(params, tokens, prefix=None, frames=None):
        logits, caches = T.prefill(
            params, tokens, ms=ms, pc=pc, max_len=sv.max_len,
            prefix_embed=prefix, enc_frames=frames, kv_mode=sv.kv_mode,
            attn_impl=sv.attn_impl, cache_dtype=sv.cache_dtype)
        return logits, caches
    return prefill_fn


def make_serve_step(ms: T.ModelStructure, pc: ParallelContext, sv: ServeConfig):
    """serve_step(params, tok [B], caches, t, key) -> (next_tok [B], caches).

    One full decode iteration: embed -> stack (1 psum per LP group phase) ->
    head -> vocab-parallel sample.
    """
    def serve_fn(params, tok, caches, t, key):
        logits, caches = T.decode_step(params, tok, caches, t, ms=ms, pc=pc,
                                       kv_mode=sv.kv_mode)
        if sv.temperature > 0:
            nxt = E.vocab_parallel_sample(logits, key, sv.temperature, pc)
        else:
            nxt = E.vocab_parallel_argmax(logits, pc)
        return nxt.astype(jnp.int32), caches
    return serve_fn


def generate(params, prompts, n_new: int, *, ms: T.ModelStructure,
             pc: ParallelContext, sv: ServeConfig, key=None,
             prefix=None, frames=None):
    """Greedy/temperature generation: returns [B, n_new] new tokens.

    The decode loop is a lax.scan (one compiled program regardless of
    n_new), carrying (tok, caches, t, key).
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    prefill_fn = make_prefill(ms, pc, sv)
    step_fn = make_serve_step(ms, pc, sv)
    logits, caches = prefill_fn(params, prompts, prefix, frames)
    if sv.temperature > 0:
        tok0 = E.vocab_parallel_sample(logits, key, sv.temperature, pc)
    else:
        tok0 = E.vocab_parallel_argmax(logits, pc)
    tok0 = tok0.astype(jnp.int32)
    t0 = prompts.shape[1] + (ms.cfg.prefix_len if prefix is not None else 0)

    def body(carry, i):
        tok, caches, key = carry
        key, sub = jax.random.split(key)
        # ``tok`` sits at absolute position t0 + i; its logits predict i+1.
        nxt, caches = step_fn(params, tok, caches, t0 + i, sub)
        return (nxt, caches, key), tok

    (last, _, _), toks = lax.scan(body, (tok0, caches, key),
                                  jnp.arange(n_new - 1))
    return jnp.concatenate([toks.T, last[:, None]], axis=1)


# ---------------------------------------------------------------------------
# Continuous batching over the paged pair-KV cache pool
# ---------------------------------------------------------------------------

def _finite_flag(pc: ParallelContext, *leaves) -> jnp.ndarray:
    """Scalar bool: every inexact leaf is fully finite (reduced over tp so
    all ranks agree — the host decision must be replicated)."""
    bad = jnp.zeros((), jnp.int32)
    for leaf in leaves:
        if jnp.issubdtype(leaf.dtype, jnp.inexact):
            bad = bad | jnp.any(~jnp.isfinite(leaf)).astype(jnp.int32)
    return pc.pmax_tp(bad) == 0


def make_paged_decode_fn(ms: T.ModelStructure, pc: ParallelContext, psv):
    """Local paged decode step: (params, caches, tok [n_slots], pos
    [n_slots], block_tables, poison [n_slots] bool, key) ->
    (next_tok [n_slots], ok [n_slots] bool, caches).

    ``ok[slot]`` is the per-row finite guard: False when the slot's logits
    hold NaN/inf (tp-reduced so every rank reports identically). ``poison``
    is the deterministic-chaos hook — True rows get their logits overwritten
    with NaN BEFORE the guard, exercising the containment path; an
    all-False mask is a bitwise no-op (``where`` with a false predicate
    returns the original lanes), so the hook costs the bit-identity
    contract nothing.

    The SAME body runs under plain jit (tp=1 engine) and inside shard_map
    over a tp mesh (``make_sharded_serve_step(paged=...)``): tok/pos/block
    tables are replicated host-side inputs, the pool's kv-head axis is the
    only sharded dim, and sampling is vocab-parallel so full logits never
    materialise.
    """
    def f(params, caches, tok, pos, bt, poison, key):
        logits, caches = T.decode_step(
            params, tok, caches, pos, ms=ms, pc=pc,
            cache_layout="paged", block_tables=bt)
        logits = jnp.where(poison[:, None], jnp.nan, logits)
        bad = jnp.any(~jnp.isfinite(logits), axis=-1).astype(jnp.int32)
        ok = pc.pmax_tp(bad) == 0
        if psv.temperature > 0:
            nxt = E.vocab_parallel_sample(logits, key, psv.temperature, pc)
        else:
            nxt = E.vocab_parallel_argmax(logits, pc)
        return nxt.astype(jnp.int32), ok, caches

    return f


def make_spec_step_fn(ms_draft: T.ModelStructure, ms: T.ModelStructure,
                      pc: ParallelContext, psv, k: int):
    """Fused speculative step: (params_draft, params, caches_draft,
    caches, tok, pos, bt, poison, remaining, key) -> (drafts [k, n],
    yhat [n*(k+1)], ok [n*(k+1)], caches_draft, caches).

    One compiled program runs the whole episode: ``k`` shallow greedy
    draft steps (the device-side twin of ``speculative.
    build_draft_step`` — same activity mask, same garbage-page masking
    for rows whose commit budget ends mid-episode), the probe-row
    packing (twin of ``speculative.build_verify_batch``), and the ONE
    full-depth verify at batch ``n*(k+1)``. Host-side acceptance is the
    only thing left outside.

    Fusing matters for throughput: a (k+1)-launch python loop pays the
    per-launch dispatch + device sync k+1 times per speculative step —
    most of a smoke-scale step's wall time, and k avoidable device
    round-trips per step on real accelerators. Bit-identity is
    unaffected: the draft and verify BODIES are the unchanged paged
    decode programs, executed in the same order on the same operands.
    Draft rows are never poisoned and their finite flags are ignored
    (garbage proposals are simply refused by the verify, whose own
    per-row ``ok`` guard is returned)."""
    draft = make_paged_decode_fn(ms_draft, pc, psv)
    verify = make_paged_decode_fn(ms, pc, psv)

    def f(params_draft, params, caches_draft, caches, tok, pos, bt,
          poison, remaining, key):
        keys = jax.random.split(key, k + 1)
        n = tok.shape[0]
        no_poison = jnp.zeros((n,), jnp.bool_)
        garbage = jnp.full_like(bt, PG.GARBAGE_PAGE)
        prev = tok
        drafts = []
        for j in range(k):
            act = (remaining >= 0) & (j <= remaining)
            tok_j = jnp.where(act, prev, 0)
            pos_j = jnp.where(act, pos + j, 0)
            bt_j = jnp.where(act[:, None], bt, garbage)
            d, _, caches_draft = draft(params_draft, caches_draft, tok_j,
                                       pos_j, bt_j, no_poison, keys[j])
            drafts.append(d)
            prev = d
        drafts = jnp.stack(drafts)
        rows = n * (k + 1)
        base = jnp.arange(n) * (k + 1)
        tok_v = jnp.zeros((rows,), jnp.int32)
        pos_v = jnp.zeros((rows,), jnp.int32)
        bt_v = jnp.full((rows, bt.shape[1]), PG.GARBAGE_PAGE, jnp.int32)
        poison_v = jnp.zeros((rows,), jnp.bool_)
        for j in range(k + 1):
            act = (remaining >= 0) & (j <= remaining)
            u = tok if j == 0 else drafts[j - 1]
            tok_v = tok_v.at[base + j].set(jnp.where(act, u, 0))
            pos_v = pos_v.at[base + j].set(jnp.where(act, pos + j, 0))
            bt_v = bt_v.at[base + j].set(jnp.where(act[:, None], bt,
                                                   garbage))
            poison_v = poison_v.at[base + j].set(poison & act)
        yhat, ok, caches = verify(params, caches, tok_v, pos_v, bt_v,
                                  poison_v, keys[k])
        return drafts, yhat, ok, caches_draft, caches

    return f


def make_paged_prefill_fn(ms: T.ModelStructure, pc: ParallelContext, psv,
                          prompt_len: int):
    """Local exact-length prefill + page scatter: (params, caches, prompt
    [1, prompt_len], page_ids, slot, key) -> (first_tok [1], ok, caches).
    ``ok`` is the finite guard over the sampled position's logits AND the
    emitted cache (a poisoned prompt/params corrupts the kv it writes, not
    just the logits — the guard must trip before those pages are ever
    donated or decoded from). The cache emission length rounds up to whole
    pages; the forward itself is the exact prompt — no padding (the
    bit-identity contract). Shared by the tp=1 jit and the shard_map
    wrapper (sp stays off: exact odd-length prompts do not split over
    ranks)."""
    n_pg = -(-prompt_len // psv.page_size)
    emit_len = n_pg * psv.page_size

    def f(params, caches, prompt, page_ids, slot, key):
        logits, _, seq = T.forward_full(
            params, prompt, ms=ms, pc=pc, emit_cache=True,
            max_len=emit_len, kv_mode="heads",
            attn_impl=BK.PREFILL_ATTN_IMPL)
        # Same cast T.prefill applies to the ring cache.
        seq = jax.tree.map(
            lambda c: c.astype(psv.cache_dtype)
            if c.dtype in (jnp.float32, jnp.bfloat16) else c, seq)
        last = logits[:, prompt_len - 1]
        ok = _finite_flag(pc, last, *jax.tree.leaves(seq))
        if psv.temperature > 0:
            tok0 = E.vocab_parallel_sample(last, key, psv.temperature, pc)
        else:
            tok0 = E.vocab_parallel_argmax(last, pc)
        caches = PG.scatter_prefill(caches, seq, page_ids, slot)
        return tok0.astype(jnp.int32), ok, caches

    return f


def make_paged_bucket_prefill_fn(ms: T.ModelStructure, pc: ParallelContext,
                                 psv, bucket: int, rows: int,
                                 ctx_pages: int = 0):
    """Bucketed batched prefill + masked page scatter: (params, caches,
    prompts [rows, bucket], true_lens [rows], page_ids [rows, n_pg],
    [ctx_ids [rows, ctx_pages], ctx_lens [rows],] key)
    -> (first_tok [rows], ok [rows], caches).

    ONE launch prefills up to ``rows`` requests right-padded to
    ``bucket`` tokens. Bit-identity with the exact-length program holds
    because the forward runs the pinned-tile chunked attention impl
    (serve.bucketing): row i's logits at position ``true_lens[i] - 1``
    depend only on kv tiles covering [0, true_lens[i]) — right-padding
    and batching cannot move a bit. The per-row finite guard covers the
    sampled logits AND the row's emitted cache (tp-reduced like the
    decode guard), so one poisoned request fails alone while its
    bucket-mates' streams stay untouched. Pad rows (group smaller than
    ``rows``) carry ``true_lens == 1`` and all-garbage page ids: their
    junk never lands (``scatter_prefill_rows`` masks garbage-directed
    chunks) and the host ignores their outputs. Shared by the tp=1 jit
    and the shard_map wrapper (``make_sharded_prefill(bucket_rows=)``).

    ``ctx_pages > 0`` makes the program CTX-AWARE (prefix-on engines):
    radix-HIT rows ride the same launch as cold rows. Row i's matched
    prefix pages arrive in ``ctx_ids[i]`` (garbage-padded to the uniform
    ``ctx_pages`` width) with its true ctx length in ``ctx_lens[i]``;
    ``prompts[i]`` then holds only the SUFFIX (true_lens[i] = suffix
    length) and the forward runs with per-row start offsets. Cold rows
    pass ctx_len 0 + all-garbage ctx ids and reduce bit-identically to
    the plain (ctx_pages=0) program: their gathered ctx is finite junk
    that the per-row key rearrangement parks past the causal horizon,
    where pinned-tile masking zeroes it exactly (see
    blocks.attention_phase_full). One arity per engine keeps prefill
    compiles <= n_buckets even at high hit-rates.
    """
    def f(params, caches, prompts, true_lens, page_ids, *rest):
        if ctx_pages:
            ctx_ids, ctx_lens, key = rest
            ctx = PG.gather_ctx_rows(caches, ctx_ids)
            start = ctx_lens
        else:
            (key,) = rest
            ctx = None
            start = 0
        logits, _, seq = T.forward_full(
            params, prompts, ms=ms, pc=pc, emit_cache=True,
            max_len=bucket, kv_mode="heads", ctx_kv=ctx, start=start,
            attn_impl=BK.PREFILL_ATTN_IMPL)
        seq = jax.tree.map(
            lambda c: c.astype(psv.cache_dtype)
            if c.dtype in (jnp.float32, jnp.bfloat16) else c, seq)
        last = jnp.take_along_axis(
            logits, (true_lens - 1)[:, None, None], axis=1)[:, 0]
        bad = jnp.any(~jnp.isfinite(last), axis=-1).astype(jnp.int32)
        for seg in seq:
            for name, c in seg.items():
                if jnp.issubdtype(c.dtype, jnp.inexact):
                    ba = T.cache_batch_axis(name)
                    ax = tuple(i for i in range(c.ndim) if i != ba)
                    bad = bad | jnp.any(~jnp.isfinite(c),
                                        axis=ax).astype(jnp.int32)
        ok = pc.pmax_tp(bad) == 0
        if psv.temperature > 0:
            tok0 = E.vocab_parallel_sample(last, key, psv.temperature, pc)
        else:
            tok0 = E.vocab_parallel_argmax(last, pc)
        caches = PG.scatter_prefill_rows(caches, seq, page_ids)
        return tok0.astype(jnp.int32), ok, caches

    return f


def make_paged_suffix_prefill_fn(ms: T.ModelStructure, pc: ParallelContext,
                                 psv, n_ctx_pages: int, suffix_len: int):
    """Prefix-hit suffix prefill: (params, caches, suffix [1, suffix_len],
    ctx_ids [n_ctx_pages], sfx_ids, slot, key) -> (first_tok [1], ok,
    caches). Gathers the matched pages as read-only context kv, runs the
    forward over ONLY the unmatched suffix, and scatters the suffix pages.
    Every suffix row reduces over exactly ``ctx + suffix`` keys — the cold
    full-prompt program's reduction shape for the same row — so greedy
    outputs stay bit-identical to a cold run (fp32 pool). Copy-on-write
    holds by construction: the program writes only ``sfx_ids`` pages,
    never ``ctx_ids``. Runs under tp > 1 too: inside shard_map a
    kv-sharded pool's ``gather_ctx`` yields each rank's local shard and
    ``_fold_ctx_kv`` branches per rank (identity vs in-gather), audited
    against the core's per-rank head count. Shared by the tp=1 jit and
    the shard_map wrapper (``make_sharded_prefill(suffix_ctx_pages=)``).
    """
    ps = psv.page_size
    start = n_ctx_pages * ps
    n_sfx = -(-suffix_len // ps)
    emit_len = n_sfx * ps

    def f(params, caches, suffix, ctx_ids, sfx_ids, slot, key):
        ctx = PG.gather_ctx(caches, ctx_ids)
        logits, _, seq = T.forward_full(
            params, suffix, ms=ms, pc=pc, emit_cache=True,
            max_len=emit_len, kv_mode="heads", ctx_kv=ctx, start=start,
            attn_impl=BK.PREFILL_ATTN_IMPL)
        seq = jax.tree.map(
            lambda c: c.astype(psv.cache_dtype)
            if c.dtype in (jnp.float32, jnp.bfloat16) else c, seq)
        last = logits[:, suffix_len - 1]
        ok = _finite_flag(pc, last, *jax.tree.leaves(seq))
        if psv.temperature > 0:
            tok0 = E.vocab_parallel_sample(last, key, psv.temperature, pc)
        else:
            tok0 = E.vocab_parallel_argmax(last, pc)
        caches = PG.scatter_prefill(caches, seq, sfx_ids, slot)
        return tok0.astype(jnp.int32), ok, caches

    return f


@dataclass(frozen=True)
class AdmissionConfig:
    """Admission-side knobs: how much prefill work a step may take on and
    how the submit queue bounds itself. ``prefill_buckets`` is the bucket
    ladder for batched prefill — None picks the auto ladder
    (``bucketing.default_buckets``), an empty tuple disables bucketing
    (every prefill runs the exact-length program — the A/B reference),
    an explicit tuple is validated against the page geometry."""
    prefill_token_budget: int = 4096
    max_queue: int = 0
    prefill_buckets: Optional[Tuple[int, ...]] = None


@dataclass(frozen=True)
class DegradeConfig:
    """Overload degradation: the aggressive-Δ slot cohort (see
    PagedServeConfig docstring)."""
    enabled: bool = False
    slots: int = 0
    queue_depth: int = 1
    eff_depth: int = 0


@dataclass(frozen=True)
class SpecConfig:
    """Self-speculative decoding: shallow-Δ drafts, full-depth verify."""
    k: int = 0
    delta: int = 0


@dataclass(frozen=True)
class TelemetryConfig:
    """Observability retention + profiling hooks."""
    enabled: bool = True
    profile_decode: bool = False


@dataclass(frozen=True)
class PagedServeConfig:
    """Static geometry of the continuous-batching engine.

    Grouped view: the flat fields below decompose into four sub-configs —
    ``AdmissionConfig`` (budget, queue bound, bucket ladder),
    ``DegradeConfig``, ``SpecConfig``, ``TelemetryConfig`` — passable as
    the ``admission`` / ``degrade`` / ``spec`` / ``telemetry_cfg``
    kwargs. The flat kwargs stay accepted as a deprecation shim (every
    existing caller passes them), and after construction BOTH views are
    populated and consistent: group kwargs are copied onto the flats,
    then the canonical group objects are rebuilt from the flats.
    ``validate()`` is the one entry point for every cross-field rule; the
    engine calls it first thing.

    max_len must be a page multiple: the decode step attends over exactly
    ``pages_per_slot * page_size == max_len`` gathered positions, the same
    horizon a ring cache of ``max_len`` gives one-shot ``generate()`` —
    equal reduction shapes are part of the bit-identity contract.
    ``n_pages`` INCLUDES the reserved garbage page 0, so the allocatable
    capacity is ``n_pages - 1`` pages.

    prefix_cache: radix prefix sharing over whole pages — matched prompt
    pages are linked read-only into the block table and only the unmatched
    suffix is prefilled. Attention-only models (the engine silently
    disables it for mixers with recurrent state). Greedy prefix-hit
    outputs are bit-identical to a cold run when the pool holds fp32 and
    the donor computed the shared pages at compatible shapes (whole-page
    chunks are length-invariant by the suffix-prefill contract; see
    EXPERIMENTS.md).
    preempt_after: > 0 enables preemption — after that many consecutive
    steps with a blocked queue head, the youngest running request is
    parked (pages donated/released, tokens kept) and later resumed via
    radix re-link + bit-exact decode replay. 0 keeps PR 2's strict FCFS.

    max_queue: > 0 bounds the SUBMIT queue. A submission against a full
    queue sheds the queued request with the slackest deadline if the
    newcomer is strictly more urgent (EXPIRED with ``LoadShedError``),
    else raises ``QueueFullError`` — overload degrades by policy, never by
    unbounded memory growth. 0 keeps the queue unbounded.
    degrade_delta: reserve ``degrade_slots`` slots as a DEGRADED cohort
    running the same weights re-paired at an aggressive Δ
    (``degrade_eff_depth`` effective layers; 0 = maximal pairing). When the
    queue depth reaches ``degrade_queue_depth`` and the main cohort is
    full, new admissions overflow into the degraded cohort instead of
    waiting — the paper's retraining-free speed/quality family as an
    overload valve. tp=1 engines only for now.
    spec_k: > 0 turns on SELF-SPECULATIVE decoding (serve.speculative):
    each step drafts ``spec_k`` greedy tokens per running slot with the
    same weights re-paired at an aggressive Δ (``spec_delta`` effective
    layers, 0 = maximal pairing), then verifies all of them in ONE
    full-depth launch of the regular decode program at batch
    ``n_main * (spec_k + 1)`` — accepting the longest matched draft
    prefix plus the verifier's bonus token, and un-writing rejected
    positions from both cache trees. Greedy output streams stay
    BIT-IDENTICAL to the non-speculative engine (every committed token is
    a full-depth argmax over a committed history); acceptance only moves
    throughput. Greedy-only, tp=1, attention-only models (auto-disables
    with a warning for recurrent mixers), exclusive with degrade_delta
    for now.
    """
    n_slots: int = 8              # concurrent decode slots (fixed batch)
    page_size: int = 16           # tokens per cache page
    n_pages: int = 129            # pool size incl. the reserved garbage page
    max_len: int = 256            # per-request position cap (page multiple)
    prefill_token_budget: int = 4096   # admission budget per step
    temperature: float = 0.0      # 0 -> greedy (bit-identical to generate())
    cache_dtype: Any = jnp.bfloat16
    eos_token: int = -1           # -1: run every request to max_new
    prefix_cache: bool = False    # radix prefix sharing (CoW pages)
    preempt_after: int = 0        # blocked-head steps before preemption
    max_queue: int = 0            # bounded submit queue (0 = unbounded)
    degrade_delta: bool = False   # aggressive-Δ overload cohort
    degrade_slots: int = 0        # slots reserved for the degraded cohort
    degrade_queue_depth: int = 1  # queue depth that signals SLO pressure
    degrade_eff_depth: int = 0    # effective depth of the cohort (0 = max Δ)
    spec_k: int = 0               # speculative draft length (0 = off)
    spec_delta: int = 0           # drafter effective depth (0 = max Δ)
    # telemetry=False drops span/gauge-series/wall retention for unbounded
    # soaks; counters, compile events and the fault log stay live (engine
    # semantics read them). Telemetry never adds device launches and never
    # changes outputs — the serve-structural gate runs a workload both ways
    # and asserts bit-identity. profile_decode brackets each cohort's
    # decode launch in a jax.profiler StepTraceAnnotation (needs an active
    # jax.profiler trace to matter; off the hot path by default).
    telemetry: bool = True        # retain spans/gauge series/wall marks
    profile_decode: bool = False  # jax.profiler annotation around decode
    # Bucketed prefill ladder: None = auto (powers-of-two page multiples
    # capped at max_len), () = off, explicit tuple = validated ladder.
    prefill_buckets: Optional[Tuple[int, ...]] = None
    # Grouped sub-config kwargs (each overrides its flat fields when
    # given; rebuilt canonically in __post_init__ so both views agree).
    admission: Optional[AdmissionConfig] = None
    degrade: Optional[DegradeConfig] = None
    spec: Optional[SpecConfig] = None
    telemetry_cfg: Optional[TelemetryConfig] = None

    def __post_init__(self):
        # Frozen dataclass: object.__setattr__ is the sanctioned escape
        # hatch inside __post_init__.
        def put(name, value):
            object.__setattr__(self, name, value)

        if self.admission is not None:
            a = self.admission
            put("prefill_token_budget", a.prefill_token_budget)
            put("max_queue", a.max_queue)
            put("prefill_buckets", a.prefill_buckets)
        if self.degrade is not None:
            d = self.degrade
            put("degrade_delta", d.enabled)
            put("degrade_slots", d.slots)
            put("degrade_queue_depth", d.queue_depth)
            put("degrade_eff_depth", d.eff_depth)
        if self.spec is not None:
            put("spec_k", self.spec.k)
            put("spec_delta", self.spec.delta)
        if self.telemetry_cfg is not None:
            put("telemetry", self.telemetry_cfg.enabled)
            put("profile_decode", self.telemetry_cfg.profile_decode)
        if self.prefill_buckets is not None:
            put("prefill_buckets", tuple(self.prefill_buckets))
        # Canonical groups, rebuilt from the (possibly shimmed) flats.
        put("admission", AdmissionConfig(
            prefill_token_budget=self.prefill_token_budget,
            max_queue=self.max_queue,
            prefill_buckets=self.prefill_buckets))
        put("degrade", DegradeConfig(
            enabled=self.degrade_delta, slots=self.degrade_slots,
            queue_depth=self.degrade_queue_depth,
            eff_depth=self.degrade_eff_depth))
        put("spec", SpecConfig(k=self.spec_k, delta=self.spec_delta))
        put("telemetry_cfg", TelemetryConfig(
            enabled=self.telemetry, profile_decode=self.profile_decode))

    def validate(self, *, mesh: bool = False) -> None:
        """Every cross-field configuration rule, in one place. Actionable
        ValueErrors, not asserts: these are mistakes a user should be
        able to fix from the message alone (validate_paged_support
        style). ``mesh``: the engine runs under a tp > 1 mesh — some
        features are tp=1-only for now."""
        if self.max_len % self.page_size != 0:
            raise ValueError(
                f"max_len={self.max_len} is not a multiple of "
                f"page_size={self.page_size}: the decode step attends over "
                "exactly pages_per_slot * page_size positions, so a partial "
                "trailing page would change reduction shapes and break the "
                "bit-identity contract — pick max_len as a whole number of "
                "pages")
        if self.n_slots < 1:
            raise ValueError(
                f"n_slots={self.n_slots} must be >= 1: the decode program's "
                "fixed batch is the slot count, and an engine with no slots "
                "can never admit a request")
        if self.max_queue < 0:
            raise ValueError(f"max_queue={self.max_queue} must be >= 0 "
                             "(0 = unbounded)")
        if self.prefill_buckets:
            BK.validate_buckets(self.prefill_buckets,
                                page_size=self.page_size,
                                max_len=self.max_len)
        if self.degrade_delta:
            if not 1 <= self.degrade_slots < self.n_slots:
                raise ValueError(
                    f"degrade_delta needs 1 <= degrade_slots < n_slots "
                    f"(got degrade_slots={self.degrade_slots}, "
                    f"n_slots={self.n_slots}): the degraded cohort must "
                    "leave at least one main slot")
            if mesh:
                raise ValueError(
                    "degrade_delta is tp=1-only for now: the degraded "
                    "cohort would need its own sharded program pair and "
                    "replanned param placement")
        elif self.degrade_slots:
            raise ValueError(
                f"degrade_slots={self.degrade_slots} without degrade_delta: "
                "reserved degraded slots would simply idle — set "
                "degrade_delta=True or degrade_slots=0")
        if self.spec_k < 0:
            raise ValueError(f"spec_k={self.spec_k} must be >= 0 (0 = off)")
        if self.spec_k:
            if self.temperature > 0:
                raise ValueError(
                    "spec_k needs temperature=0.0: acceptance compares "
                    "greedy argmax ids — sampled verification would need "
                    "rejection sampling over full logit distributions, "
                    "which the vocab-parallel sampler never materialises")
            if mesh:
                raise ValueError(
                    "spec_k is tp=1-only for now: the draft and wide "
                    "verify programs need their own sharded wrappers and "
                    "replanned param placement")
            if self.degrade_delta:
                raise ValueError(
                    "spec_k is exclusive with degrade_delta for now: the "
                    "speculative controller drives the main cohort, and "
                    "composing it with a degraded cohort needs a draft "
                    "tree per cohort — pick one overload strategy")
        elif self.spec_delta:
            raise ValueError(
                f"spec_delta={self.spec_delta} without spec_k: set "
                "spec_k >= 1 to enable speculative decoding")

    @property
    def pages_per_slot(self) -> int:
        return self.max_len // self.page_size


class PagedEngine:
    """Continuous-batching serving engine: ``add_request / step / drain``.

    One ``step()`` is: chaos injection (when armed) -> deadline expiry ->
    FCFS admission (each admitted request prefills at its exact length and
    claims its pages; prompts and prefill outputs pass fault guards), then
    ONE fixed-shape decode program per ACTIVE cohort. Finished requests
    (EOS / max_new) release their slot and pages the same step, so the next
    admission reuses them; FAILED/CANCELLED/EXPIRED requests release within
    the step that terminates them.

    Greedy outputs are bit-identical per request to one-shot
    ``generate(params, prompt[None], max_new)`` with ``max_len`` equal to
    this engine's: prefill runs the identical forward at the exact prompt
    length, decode runs the identical per-row math (paged gather + same
    cores), and every cross-request interaction is row-independent — which
    is also why failing one slot leaves the survivors' streams untouched.

    ``mesh``: run the compiled programs under shard_map on a tp > 1 mesh
    (``ms`` must be built with the matching tp). The page pool shards its
    kv-head axis over the model axis like the ring cache; scheduling,
    block tables and per-slot positions stay host-side and tp-agnostic.
    The radix prefix cache runs under tp > 1 too: gathered ctx kv folds
    per rank (kv-sharded pool: identity; replicated pool: in-gather like
    the paged decode kernel's head map), so prefix-hit streams stay
    bit-identical to the tp=1 prefix-on engine and to sharded one-shot
    ``generate()``.

    ``fault_plan``: a ``repro.serve.faults.FaultPlan`` — each step applies
    that step's scheduled events through the same hooks real faults would
    take; ``fault_log`` records what actually fired (rid-stamped), making
    every outcome reproducible by (seed, step).
    """

    def __init__(self, params, ms: T.ModelStructure, psv: PagedServeConfig,
                 *, pc: Optional[ParallelContext] = None, key=None,
                 mesh=None, fault_plan: Optional[F.FaultPlan] = None):
        # Cross-field configuration rules live on the config itself
        # (PagedServeConfig.validate) — the engine calls it first thing,
        # then checks only what needs the model structure or mesh/pc.
        psv.validate(mesh=mesh is not None)
        PG.validate_paged_support(ms, psv.max_len)
        self.ms = ms
        self.psv = psv
        self.mesh = mesh
        self.n_main = psv.n_slots - (psv.degrade_slots
                                     if psv.degrade_delta else 0)
        self.n_deg = psv.n_slots - self.n_main
        # Degraded-cohort model: the SAME weights re-paired under an
        # aggressive Δ plan (retraining-free — repro.core.lp.replan), built
        # from the raw host params before any device placement.
        self.ms_deg = self.params_deg = None
        if psv.degrade_delta:
            cfg = ms.cfg
            if psv.degrade_eff_depth > 0:
                deg_plan = LP.plan_for_depth(cfg, psv.degrade_eff_depth,
                                             end=cfg.n_layers)
            else:
                deg_plan = LP.plan_range(cfg, 0, cfg.n_layers)
            if len(deg_plan.pairs) <= len(ms.plan.pairs):
                raise ValueError(
                    f"degraded plan pairs {len(deg_plan.pairs)} layers vs "
                    f"base {len(ms.plan.pairs)}: the degraded cohort must "
                    "be strictly MORE aggressive than the base plan "
                    "(lower degrade_eff_depth, or use a shallower base)")
            segs2, sp2 = LP.replan(cfg, params["segments"], ms.segments,
                                   deg_plan)
            self.ms_deg = T.build_structure(cfg, plan=deg_plan, tp=ms.tp)
            assert tuple(s.group.specs for s in self.ms_deg.segments) == \
                tuple(s.group.specs for s in segs2)
            self.params_deg = dict(params, segments=sp2)
        # Speculative drafter: the SAME weights re-paired at an aggressive
        # Δ (serve.speculative) — the paper's shallow configuration as a
        # free draft model. Eligibility-gated like the prefix cache:
        # recurrent mixers auto-disable with a warning instead of erroring,
        # and the engine then behaves exactly as spec_k=0 (bit-identical —
        # the fallback test pins it).
        self.spec_k = psv.spec_k
        self.ms_draft = self.params_draft = None
        if self.spec_k and not SP.spec_eligible(ms):
            warnings.warn(
                f"{ms.cfg.name}: speculative decoding auto-disabled — "
                "recurrent mixer state (mamba conv/h, RG-LRU h) advances "
                "on every launch and has no per-position representation "
                "to rewind (per-draft-step state snapshots are a "
                "follow-on); serving continues non-speculatively",
                stacklevel=2)
            self.spec_k = 0
        if self.spec_k:
            cfg = ms.cfg
            spec_plan = SP.draft_plan_for(cfg, ms.plan, psv.spec_delta)
            segs2, sp2 = LP.replan(cfg, params["segments"], ms.segments,
                                   spec_plan)
            self.ms_draft = T.build_structure(cfg, plan=spec_plan, tp=ms.tp)
            assert tuple(s.group.specs for s in self.ms_draft.segments) == \
                tuple(s.group.specs for s in segs2)
            self.params_draft = dict(params, segments=sp2)
        if mesh is not None:
            if pc is not None:
                raise ValueError(
                    "pass mesh OR pc, not both: with a mesh the engine "
                    "derives its ParallelContext from the mesh axes")
            self.pc = make_context(mesh, sp=False)
            if self.pc.tp_size != ms.tp:
                raise ValueError(
                    f"mesh model axis has {self.pc.tp_size} devices but ms "
                    f"was built with tp={ms.tp}: rebuild the structure with "
                    f"build_structure(cfg, tp={self.pc.tp_size}) (params "
                    "must be initialised/loaded at that tp as well)")
            self.params = jax.device_put(params, _tree_shardings(
                mesh, T.param_pspecs(ms)))
        else:
            self.pc = pc if pc is not None else ParallelContext()
            self.params = params
        # ONE instrumented path for every engine event: counters, spans,
        # gauges, compile events and fault records all live here (host-side
        # only — telemetry never adds device launches). Must exist before
        # the scheduler (span emission) and the compiled programs (compile
        # events).
        self.telemetry = Telemetry(enabled=psv.telemetry)
        self.telemetry.seed_counters(self.COUNTER_KEYS)
        self.telemetry.fault_counts.update(
            {k: 0 for k in F.ALL_FAULT_KINDS})
        # ONE home for every compiled program, keyed (cohort, program,
        # shape) — the same triple the telemetry compile-event stream
        # uses, so cache misses and compile accounting can never drift.
        self._programs = ProgramCache(self.telemetry)
        self.pool = PagePool(psv.n_pages)
        self.prefix = (PrefixCache(psv.page_size, telemetry=self.telemetry)
                       if psv.prefix_cache and self._prefix_eligible(ms)
                       else None)
        # Bucketed prefill needs the pinned-tile chunked impl's padding
        # transparency, which only the attention mixer family honours —
        # same eligibility gate as the prefix cache. None = auto ladder,
        # () = off (the exact-length A/B reference configuration).
        if psv.prefill_buckets == () or not self._prefix_eligible(ms):
            self._buckets: Tuple[int, ...] = ()
        elif psv.prefill_buckets is None:
            self._buckets = BK.default_buckets(psv.max_len, psv.page_size)
        else:
            self._buckets = psv.prefill_buckets
        self.sched = Scheduler(
            n_slots=psv.n_slots, pool=self.pool, page_size=psv.page_size,
            max_len=psv.max_len,
            prefill_token_budget=psv.prefill_token_budget,
            prefix_cache=self.prefix, preempt_after=psv.preempt_after,
            degrade_slots=self.n_deg, telemetry=self.telemetry,
            prefill_buckets=self._buckets)
        if mesh is not None:
            c_abs, c_specs = PG.paged_cache_meta(
                ms, n_slots=self.n_main, n_pages=psv.n_pages,
                page_size=psv.page_size, dtype=psv.cache_dtype)
            # Made already sharded: no device holds the whole pool.
            self.caches = jax.jit(
                lambda: jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                                     c_abs),
                out_shardings=_tree_shardings(mesh, c_specs))()
        else:
            self.caches = PG.init_paged_caches(
                ms, n_slots=self.n_main, n_pages=psv.n_pages,
                page_size=psv.page_size, dtype=psv.cache_dtype)
        # The degraded cohort's cache tree spans the SAME page-id space
        # (one host-side PagePool partitions ids between cohorts by
        # allocation, not by range) but holds aggressive-plan kv.
        self.caches_deg = (PG.init_paged_caches(
            self.ms_deg, n_slots=self.n_deg, n_pages=psv.n_pages,
            page_size=psv.page_size, dtype=psv.cache_dtype)
            if self.n_deg else None)
        # The drafter's cache tree spans the SAME page-id space as the
        # main tree (one block table serves both); it holds
        # aggressive-plan kv that only ever feeds draft proposals — the
        # verify launch reads the MAIN tree, so draft bits can move
        # acceptance but never committed output.
        self.caches_draft = (PG.init_paged_caches(
            self.ms_draft, n_slots=self.n_main, n_pages=psv.n_pages,
            page_size=psv.page_size, dtype=psv.cache_dtype)
            if self.spec_k else None)
        P_slot = psv.pages_per_slot
        self.block_tables = np.full((self.n_main, P_slot), PG.GARBAGE_PAGE,
                                    np.int32)
        self.tok = np.zeros((self.n_main,), np.int32)
        self.pos = np.zeros((self.n_main,), np.int32)
        self.block_tables_deg = np.full((self.n_deg, P_slot),
                                        PG.GARBAGE_PAGE, np.int32)
        self.tok_deg = np.zeros((self.n_deg,), np.int32)
        self.pos_deg = np.zeros((self.n_deg,), np.int32)
        self._key = key if key is not None else jax.random.PRNGKey(0)
        self.step_count = 0
        self.results: Dict[int, np.ndarray] = {}
        self._requests: Dict[int, Request] = {}
        self._decode = self._make_decode(COHORT_MAIN)
        self._decode_deg = (self._make_decode(COHORT_DEGRADED)
                            if self.n_deg else None)
        self._spec_step = None
        if self.spec_k:
            # ONE fused program holds both speculative bodies: the
            # k-step draft episode at the aggressive plan (batch n_main)
            # and the verifier — which IS the regular decode program at
            # a wider batch: n_main * (spec_k + 1) probe rows through
            # the same body the main cohort compiles at n_main (row
            # independence is what makes the wide launch bit-equal to
            # sequential steps). One build, one compile event per body:
            # the fused program lives under the draft key and the verify
            # body is note()d so the compile stream still shows both.
            self._spec_step = self._programs.get(
                SP.COHORT_SPEC_DRAFT, "decode", self.n_main,
                lambda: jax.jit(
                    make_spec_step_fn(self.ms_draft, ms, self.pc, psv,
                                      self.spec_k),
                    donate_argnums=(2, 3)))
            self._programs.note(SP.COHORT_SPEC_VERIFY, "decode",
                                self.n_main * (self.spec_k + 1))
        # rids whose draft tree was primed by a bucketed draft-cohort
        # prefill this step — _spec_prime then skips its full prefill.
        self._spec_primed: set = set()
        # Greedy + fp32 pool => suffix/replay recomputation is bit-exact
        # against the original run; the engine then self-checks the replay.
        self._exact = (psv.temperature == 0.0
                       and psv.cache_dtype == jnp.float32)
        # Chaos state: the plan schedules, the engine applies + logs.
        self._plan = fault_plan
        self._poison_slots: set = set()   # slots NaN-poisoned THIS step
        self._poison_next = 0             # deferred poison_prompt events
        self._storm_next = 0              # deferred deadline_storm victims

    #: Every monotone engine counter, pre-registered at 0. Per-step
    #: ``step()`` stats are DELTAS of the lifecycle subset over the step —
    #: one increment site per event, no parallel stats threading.
    COUNTER_KEYS = (
        "prefill_tokens", "hit_tokens", "resume_hit_tokens",
        "replay_tokens", "full_prefills", "suffix_prefills", "prefix_hits",
        "bucket_prefills", "bucket_groups", "pad_tokens",
        "submitted", "admitted", "decoded", "finished", "preempted",
        "failed", "expired", "cancelled", "shed", "degraded_admissions",
        "draft_steps", "verify_steps", "spec_accepted", "spec_rejected",
        "spec_rewound")
    #: The subset ``step()`` reports as per-step deltas.
    STEP_STAT_KEYS = ("admitted", "decoded", "finished", "preempted",
                      "failed", "expired")

    @property
    def counters(self) -> Dict[str, int]:
        """Monotone event counters (the live Telemetry dict)."""
        return self.telemetry.counters

    @property
    def fault_log(self) -> List[Dict[str, Any]]:
        return self.telemetry.fault_log

    @property
    def fault_counts(self) -> Dict[str, int]:
        return self.telemetry.fault_counts

    @staticmethod
    def _prefix_eligible(ms: T.ModelStructure) -> bool:
        """Prefix sharing resumes from cached kv alone: every mixer must be
        attention (recurrent conv/h state has no page representation) and
        the FFN a plain MLP (the MoE pair path has no pinned-order
        projection; see model.mlp.mlp_forward)."""
        return all(spec.mixer.startswith("attn") and not spec.cross_attn
                   and spec.ffn in ("mlp", None)
                   for seg in ms.segments for spec in seg.group.specs)

    # -- cohort plumbing ------------------------------------------------
    def _cohort_of_slot(self, slot: int) -> str:
        return COHORT_MAIN if slot < self.n_main else COHORT_DEGRADED

    def _arrays(self, cohort: str):
        """(tok, pos, block_tables, slot_base) for a cohort; slot indices
        into these arrays are ``global_slot - slot_base``."""
        if cohort == COHORT_MAIN:
            return self.tok, self.pos, self.block_tables, 0
        return self.tok_deg, self.pos_deg, self.block_tables_deg, self.n_main

    def _model(self, cohort: str):
        if cohort == COHORT_MAIN:
            return self.params, self.ms
        return self.params_deg, self.ms_deg

    def _get_caches(self, cohort: str):
        return self.caches if cohort == COHORT_MAIN else self.caches_deg

    def _set_caches(self, cohort: str, val) -> None:
        if cohort == COHORT_MAIN:
            self.caches = val
        else:
            self.caches_deg = val

    def _decode_fn(self, cohort: str):
        return self._decode if cohort == COHORT_MAIN else self._decode_deg

    # -- compiled programs ---------------------------------------------
    # Every builder below is compile-event-FREE: callers route through
    # ``self._programs.get(cohort, program, shape, build)``, which emits
    # the compile event exactly once per distinct key — the single
    # compile-accounting increment site.
    def _make_decode(self, cohort: str):
        size = self.n_main if cohort == COHORT_MAIN else self.n_deg

        def build():
            params_ms = self._model(cohort)[1] \
                if cohort == COHORT_DEGRADED else self.ms
            if self.mesh is not None:
                fn, _, _, _ = make_sharded_serve_step(
                    params_ms, self.mesh, None, batch=size, paged=self.psv)
                return fn
            local = make_paged_decode_fn(params_ms, self.pc, self.psv)
            return jax.jit(local, donate_argnums=(1,))

        return self._programs.get(cohort, "decode", size, build)

    def _prefill_fn(self, prompt_len: int, cohort: str):
        """Exact-length prefill + page scatter, compiled once per distinct
        (prompt length, cohort) — the cohorts differ in both the model
        structure (re-paired stack) and the cache tree's slot count."""
        def build():
            ms = self._model(cohort)[1]
            size = self.n_main if cohort == COHORT_MAIN else self.n_deg
            if self.mesh is not None:
                fn, _, _ = make_sharded_prefill(
                    ms, self.mesh, None, batch=1, prompt_len=prompt_len,
                    paged=self.psv, paged_slots=size)
                return fn
            local = make_paged_prefill_fn(ms, self.pc, self.psv, prompt_len)
            return jax.jit(local, donate_argnums=(1,))

        return self._programs.get(cohort, "prefill_full", prompt_len, build)

    def _bucket_ctx_pages(self, cohort: str) -> int:
        """Ctx-page width of the cohort's bucket programs. Prefix-ON main
        cohorts route EVERY bucket launch through the ctx-aware program
        (cold rows pass ctx_len 0 + all-garbage ids and reduce
        bit-identically to the plain program), so hits and colds share one
        compile and the ladder bound holds with hits present. The width is
        uniform: a radix match always leaves a >= 2-token (>= 1-page)
        suffix (scheduler._match_cap), so ctx pages <= pages_per_slot - 1.
        Draft-mirror and degraded launches keep the plain program (the
        radix tree never holds their plan's pages)."""
        if self.prefix is not None and cohort == COHORT_MAIN:
            return self.psv.pages_per_slot - 1
        return 0

    def _bucket_prefill_fn(self, bucket: int, rows: int, cohort: str):
        """Bucketed batched prefill: ``rows`` right-padded prompts through
        one ``[rows, bucket]`` launch. Compiled once per distinct
        (bucket, rows) — and rows is a pure function of (bucket, static
        config), so the cohort's compile count is bounded by the ladder
        length, not by arrivals. Prefix-on main cohorts build the
        ctx-aware arity (``_bucket_ctx_pages``) so radix-hit suffixes ride
        the same launch."""
        ctx_pages = self._bucket_ctx_pages(cohort)

        def build():
            if self.mesh is not None:
                fn, _, _ = make_sharded_prefill(
                    self.ms, self.mesh, None, batch=rows,
                    prompt_len=bucket, paged=self.psv,
                    paged_slots=self.n_main, bucket_rows=rows,
                    bucket_ctx_pages=ctx_pages)
                return fn
            ms = (self.ms_draft if cohort == SP.COHORT_SPEC_DRAFT
                  else self._model(cohort)[1])
            local = make_paged_bucket_prefill_fn(ms, self.pc, self.psv,
                                                 bucket, rows, ctx_pages)
            return jax.jit(local, donate_argnums=(1,))

        return self._programs.get(cohort, "prefill_bucket", (bucket, rows),
                                  build)

    def _suffix_fn(self, n_ctx_pages: int, suffix_len: int):
        """Prefix-hit exact-shape prefill, compiled once per (context
        pages, suffix length) — the fallback when the suffix misses the
        bucket ladder. Main cohort only (the radix tree never holds
        degraded-plan pages); runs under tp > 1 via the shard_map wrapper
        (the per-rank ctx fold in model.blocks)."""
        if self.mesh is not None:
            fn, _, _ = make_sharded_prefill(
                self.ms, self.mesh, None, batch=1, prompt_len=suffix_len,
                paged=self.psv, paged_slots=self.n_main,
                suffix_ctx_pages=n_ctx_pages)
            return fn
        local = make_paged_suffix_prefill_fn(self.ms, self.pc, self.psv,
                                             n_ctx_pages, suffix_len)
        return jax.jit(local, donate_argnums=(1,))

    def _draft_decode_fn(self):
        """Single-step draft decode, compiled lazily — only the resume
        catch-up path needs it (the decode phase runs the fused
        ``_draft_episode`` program instead)."""
        return self._programs.get(
            SP.COHORT_SPEC_DRAFT, "decode_catchup", self.n_main,
            lambda: jax.jit(
                make_paged_decode_fn(self.ms_draft, self.pc, self.psv),
                donate_argnums=(1,)))

    def _spec_prefill_fn(self, prompt_len: int):
        """Draft-tree prefill at the aggressive plan, compiled once per
        distinct prompt length (tp=1 only — spec_k validation)."""
        return self._programs.get(
            SP.COHORT_SPEC_DRAFT, "prefill_full", prompt_len,
            lambda: jax.jit(
                make_paged_prefill_fn(self.ms_draft, self.pc, self.psv,
                                      prompt_len),
                donate_argnums=(1,)))

    def _scrub_fn(self, cohort: str):
        """Compiled page/state scrub for one cohort (built lazily — the
        happy path never needs it). Fixed shapes: the page-id vector is
        padded with the garbage page."""
        def build():
            if self.mesh is not None:
                _, c_specs = PG.paged_cache_meta(
                    self.ms, n_slots=self.n_main, n_pages=self.psv.n_pages,
                    page_size=self.psv.page_size, dtype=self.psv.cache_dtype)
                wrapped = jax.shard_map(PG.scrub_pages, mesh=self.mesh,
                                    in_specs=(c_specs, P(), P()),
                                    out_specs=c_specs, check_vma=False)
                return jax.jit(wrapped, donate_argnums=(0,))
            return jax.jit(PG.scrub_pages, donate_argnums=(0,))

        return self._programs.get(cohort, "scrub",
                                  self.psv.pages_per_slot, build)

    # -- public API ----------------------------------------------------
    def add_request(self, prompt, max_new: int,
                    eos_token: Optional[int] = None,
                    deadline: Optional[int] = None) -> int:
        """Queue a request; returns its id. Submit-time validation
        (``Scheduler.submit``) rejects malformed work with typed
        ``InvalidRequestError``s; the engine adds the vocabulary-range
        check (only it knows the model) and the bounded-queue policy.

        ``deadline``: ABSOLUTE engine step by which the request must
        finish; at the first step boundary where ``step_count >= deadline``
        it is EXPIRED and releases everything. None = no deadline.
        """
        arr = np.asarray(prompt)
        if arr.size and np.issubdtype(arr.dtype, np.integer):
            vocab = self.ms.cfg.vocab_size
            if (arr < 0).any() or (arr >= vocab).any():
                raise InvalidRequestError(
                    f"prompt holds token ids outside [0, {vocab}): "
                    f"min={int(arr.min())}, max={int(arr.max())}")
        if self.psv.max_queue and self.sched.n_queued >= self.psv.max_queue:
            self._shed_for(deadline)
        eos = self.psv.eos_token if eos_token is None else eos_token
        r = self.sched.submit(prompt, max_new, eos,
                              deadline=-1 if deadline is None else deadline,
                              step=self.step_count)
        self._requests[r.rid] = r
        # Deferred chaos events that needed a submission to land on.
        if self._poison_next > 0:
            self._poison_next -= 1
            r.prompt = r.prompt.copy()
            r.prompt[r.rid % r.prompt_len] = self.ms.cfg.vocab_size + 1
            self._log_fault(F.POISON_PROMPT, rid=r.rid, deferred=True)
        if self._storm_next > 0:
            self._storm_next -= 1
            r.deadline = self.step_count
            self._log_fault(F.DEADLINE_STORM, rid=r.rid, deferred=True)
        return r.rid

    def cancel(self, rid: int) -> bool:
        """Client-initiated abort. True when the request was live (now
        CANCELLED, slot and pages released immediately); False when it had
        already reached a terminal state (results are whatever it produced
        first). Unknown rids raise KeyError."""
        r = self._requests[rid]
        if r.status in TERMINAL_STATES:
            return False
        slot = r.slot
        self.sched.cancel(r, self.step_count)
        if slot >= 0:
            self._clear_slot(slot)
        self.results[rid] = np.asarray(r.out, np.int32)
        return True

    def _shed_for(self, newcomer_deadline: Optional[int]) -> None:
        """Bounded-queue policy: the queue is full. Shed the queued request
        with the SLACKEST deadline if the newcomer is strictly more urgent;
        otherwise reject the newcomer (``QueueFullError``). No-deadline
        requests are infinitely slack, so any deadlined newcomer displaces
        one; a no-deadline newcomer never displaces anything."""
        inf = float("inf")
        nd = inf if newcomer_deadline is None else newcomer_deadline
        victim = max(self.sched.queue,
                     key=lambda r: (inf if r.deadline < 0 else r.deadline,
                                    r.rid))
        vd = inf if victim.deadline < 0 else victim.deadline
        if nd >= vd:
            raise QueueFullError(
                f"queue at max_queue={self.psv.max_queue} and no queued "
                f"request is slacker than the newcomer (deadline "
                f"{newcomer_deadline})")
        self.sched.expire(victim, self.step_count, error=LoadShedError(
            f"rid={victim.rid} (deadline {victim.deadline}) shed for a "
            f"more urgent arrival (deadline {newcomer_deadline})"))
        self.results[victim.rid] = np.asarray(victim.out, np.int32)

    # -- fault containment ---------------------------------------------
    def _clear_slot(self, slot: int) -> None:
        tok, pos, bt, lo = self._arrays(self._cohort_of_slot(slot))
        bt[slot - lo] = PG.GARBAGE_PAGE
        tok[slot - lo] = 0
        pos[slot - lo] = 0

    def _scrub_slot(self, r: Request, private: List[int]) -> None:
        cohort = self._cohort_of_slot(r.slot)
        _, _, _, lo = self._arrays(cohort)
        ids = np.full((self.psv.pages_per_slot,), PG.GARBAGE_PAGE, np.int32)
        ids[:len(private)] = private
        fn = self._scrub_fn(cohort)
        self._set_caches(cohort, fn(self._get_caches(cohort),
                                    jnp.asarray(ids),
                                    jnp.int32(r.slot - lo)))
        if self.spec_k and cohort == COHORT_MAIN:
            # The draft tree scattered the same (possibly poisoned)
            # request's kv into the same page ids — scrub it too before
            # the pages return to the free list.
            fn = self._scrub_fn(SP.COHORT_SPEC_DRAFT)
            self.caches_draft = fn(self.caches_draft, jnp.asarray(ids),
                                   jnp.int32(r.slot - lo))

    def _fail(self, r: Request, error, *, scrub: bool) -> None:
        """Contain a per-request fault: FAILED terminal state, slot row
        cleared, all pages released this step. The FAILED transition (and
        its counter) is the scheduler's ``fail`` — one increment site.
        ``scrub``: the request may have written non-finite values into its
        pages — zero its PRIVATE pages before they return to the free
        list, and purge its own radix donations (defense in depth; see
        PrefixCache.purge_pages)."""
        slot = r.slot
        if slot >= 0 and scrub:
            private = r.pages[r.n_shared:]
            if private:
                self._scrub_slot(r, private)
        donated = list(r.donated_pages)
        self.sched.fail(r, self.step_count, error)
        if slot >= 0:
            self._clear_slot(slot)
        if scrub and donated and self.prefix is not None:
            self.prefix.purge_pages(donated, self.pool)
        self.results[r.rid] = np.asarray(r.out, np.int32)

    def _expire_pass(self) -> None:
        """Deadlines are honored at step boundaries: any live request whose
        deadline has passed is EXPIRED and releases everything now."""
        sc = self.step_count
        for r in [x for x in list(self.sched.queue)
                  if 0 <= x.deadline <= sc]:
            self.sched.expire(r, sc)
            self.results[r.rid] = np.asarray(r.out, np.int32)
        for r in [x for x in list(self.sched.running.values())
                  if 0 <= x.deadline <= sc]:
            slot = r.slot
            self.sched.expire(r, sc)
            self._clear_slot(slot)
            self.results[r.rid] = np.asarray(r.out, np.int32)

    def _validate_block_tables(self) -> None:
        """Pre-launch guard: every running slot's host block-table row must
        be exactly its request's pages followed by garbage padding. A
        mismatch (cosmic ray, buggy host code, injected corruption) would
        make the decode gather read/write pages the request does not own —
        caught HERE, it costs one request instead of silently corrupting
        whichever request owns the foreign page."""
        P_slot = self.psv.pages_per_slot
        for slot, r in sorted(self.sched.running.items()):
            _, _, bt, lo = self._arrays(self._cohort_of_slot(slot))
            expect = np.full((P_slot,), PG.GARBAGE_PAGE, np.int32)
            expect[:len(r.pages)] = r.pages
            if not np.array_equal(bt[slot - lo], expect):
                self._fail(r, BlockTableCorruptionError(
                    f"rid={r.rid} slot {slot}: block-table row "
                    f"{bt[slot - lo].tolist()} != owned pages "
                    f"{r.pages}"), scrub=False)

    # -- chaos ----------------------------------------------------------
    def _log_fault(self, kind: str, *, rid: Optional[int] = None,
                   slot: Optional[int] = None, applied: bool = True,
                   deferred: bool = False) -> None:
        self.telemetry.fault(self.step_count, kind, rid=rid, slot=slot,
                             applied=applied, deferred=deferred)

    def _inject(self) -> None:
        """Apply this step's scheduled fault events. Victim selection is a
        pure function of the (deterministic) engine state, so a fixed
        (seed, workload) reproduces the exact same fault_log and results —
        the property the chaos gate asserts by running the plan twice."""
        for ev in self._plan.at(self.step_count):
            if ev.kind == F.PAGE_ALLOC_FAIL:
                self.pool.fail_next_allocs(ev.payload)
                self._log_fault(ev.kind)
            elif ev.kind == F.NAN_LOGITS:
                slots = sorted(self.sched.running)
                if not slots:
                    self._log_fault(ev.kind, applied=False)
                    continue
                slot = slots[ev.index % len(slots)]
                self._poison_slots.add(slot)
                self._log_fault(ev.kind, rid=self.sched.running[slot].rid,
                                slot=slot)
            elif ev.kind == F.BLOCK_TABLE_CORRUPT:
                slots = sorted(self.sched.running)
                if not slots:
                    self._log_fault(ev.kind, applied=False)
                    continue
                slot = slots[ev.index % len(slots)]
                r = self.sched.running[slot]
                _, _, bt, lo = self._arrays(self._cohort_of_slot(slot))
                col = ev.index % self.psv.pages_per_slot
                bt[slot - lo, col] = (int(bt[slot - lo, col]) + ev.payload) \
                    % self.psv.n_pages
                self._log_fault(ev.kind, rid=r.rid, slot=slot)
            elif ev.kind == F.POISON_PROMPT:
                queued = [q for q in self.sched.queue]
                if not queued:
                    self._poison_next += 1
                    self._log_fault(ev.kind, applied=False, deferred=True)
                    continue
                r = queued[ev.index % len(queued)]
                r.prompt = r.prompt.copy()
                r.prompt[ev.index % r.prompt_len] = \
                    self.ms.cfg.vocab_size + ev.payload
                self._log_fault(ev.kind, rid=r.rid)
            elif ev.kind == F.DEADLINE_STORM:
                queued = [q for q in self.sched.queue][:ev.payload]
                if not queued:
                    self._storm_next += ev.payload
                    self._log_fault(ev.kind, applied=False, deferred=True)
                    continue
                for r in queued:
                    r.deadline = self.step_count
                    self._log_fault(ev.kind, rid=r.rid)

    # -- per-request device work ----------------------------------------
    def _run_prefill(self, r: Request, ctx: int) -> Tuple[int, bool]:
        """Stage-1 forward over the unmatched prompt suffix (the full
        prompt when ctx == 0). Returns (token sampled from the last prompt
        position's logits, finite-guard flag)."""
        ps = self.psv.page_size
        Lp = r.prompt_len
        n_pg_prompt = -(-Lp // ps)
        cohort = r.cohort
        caches = self._get_caches(cohort)
        params = self._model(cohort)[0]
        _, _, _, lo = self._arrays(cohort)
        slot = jnp.int32(r.slot - lo)
        self._key, sub = jax.random.split(self._key)
        if ctx == 0:
            fn = self._prefill_fn(Lp, cohort)
            tok0, ok, caches = fn(
                params, caches, jnp.asarray(r.prompt[None]),
                jnp.asarray(r.pages[:n_pg_prompt], jnp.int32), slot, sub)
            self.counters["prefill_tokens"] += Lp
            self.counters["full_prefills"] += 1
        else:
            m = ctx // ps
            Ls = Lp - ctx
            fn = self._programs.get(COHORT_MAIN, "prefill_suffix", (m, Ls),
                                    lambda: self._suffix_fn(m, Ls))
            tok0, ok, caches = fn(
                params, caches, jnp.asarray(r.prompt[None, ctx:]),
                jnp.asarray(r.pages[:m], jnp.int32),
                jnp.asarray(r.pages[m:n_pg_prompt], jnp.int32), slot, sub)
            self.counters["prefill_tokens"] += Ls
            self.counters["suffix_prefills"] += 1
        self._set_caches(cohort, caches)
        return int(tok0[0]), bool(ok)

    def _replay(self, r: Request, start: int) -> bool:
        """Resume catch-up: teacher-force the parked generated tokens whose
        kv fell outside the surviving radix prefix through the REGULAR
        decode program (all other slots masked to the garbage page, their
        rows ignored). Position p re-runs the exact computation that
        produced it originally — same program, same token, same kv bits —
        so with greedy sampling the replayed prediction must reproduce the
        parked token, which the engine asserts (the continuous form of the
        preempt-resume bit-identity gate). Returns False if the finite
        guard trips mid-replay (the caller fails the request).

        Recurrent state (mamba/rec conv/h) needs explicit protection: the
        masked slots' ATTENTION writes land on the garbage page, but the
        decode program advances EVERY slot's state each call — replay
        would corrupt concurrently running requests. The engine snapshots
        the state entries before replaying and restores every row except
        the replaying slot's afterwards (their true timeline has no step
        here)."""
        cohort = r.cohort
        tok_a, pos_a, bt_a, lo = self._arrays(cohort)
        size = tok_a.shape[0]
        loc = r.slot - lo
        decode = self._decode_fn(cohort)
        params = self._model(cohort)[0]
        Lp = r.prompt_len
        end = Lp + len(r.out) - 1      # exclusive; kv for end-1 is the
        if start >= end:               # resumed decode step's own write
            return True
        self.telemetry.span_event(r.rid, REPLAY, self.step_count,
                                  tokens=end - start)
        caches = self._get_caches(cohort)
        state_saved = [
            {name: np.asarray(v) for name, v in seg.items()
             if not PG.is_paged_entry(name)} for seg in caches]
        no_poison = jnp.zeros((size,), jnp.bool_)
        survived = True
        for p in range(start, end):
            tok_v = np.zeros((size,), np.int32)
            pos_v = np.zeros((size,), np.int32)
            bt = np.full_like(bt_a, PG.GARBAGE_PAGE)
            tok_v[loc] = r.out[p - Lp]
            pos_v[loc] = p
            bt[loc] = bt_a[loc]
            self._key, sub = jax.random.split(self._key)
            nxt, ok, caches = decode(
                params, caches, jnp.asarray(tok_v),
                jnp.asarray(pos_v), jnp.asarray(bt), no_poison, sub)
            if not bool(np.asarray(ok)[loc]):
                survived = False
                break
            if self._exact:
                got = int(np.asarray(nxt)[loc])
                assert got == r.out[p - Lp + 1], (
                    f"replay divergence at pos {p}: {got} != "
                    f"{r.out[p - Lp + 1]} (rid={r.rid})")
            self.counters["replay_tokens"] += 1
        for seg, saved in zip(caches, state_saved):
            for name, host in saved.items():
                sl = (slice(None),) * T.cache_batch_axis(name) + (loc,)
                merged = host.copy()
                merged[sl] = np.asarray(seg[name])[sl]
                # Re-place at the entry's current sharding: under a mesh the
                # state entries are model-sharded and a bare jnp.asarray
                # would silently collapse them onto one device.
                seg[name] = jax.device_put(merged, seg[name].sharding)
        self._set_caches(cohort, caches)
        return survived

    def _spec_prime(self, r: Request) -> None:
        """Warm the DRAFT cache tree for a freshly-started request: a full
        prompt prefill at the aggressive plan, then teacher-forced
        catch-up over any parked generated tokens (the resume path).

        Always the FULL prompt, even on a radix hit: draft kv has no page
        representation in the radix tree (its bits are plan-specific), but
        re-deriving it over shared pages is idempotent — same tokens at
        the same positions produce the same draft bits — which is why
        speculation composes with the prefix cache. Quality-only work:
        the verify launch reads the MAIN tree, so nothing here can move
        committed output, and the finite guards are ignored for the same
        reason (non-finite draft kv yields garbage proposals the verifier
        simply refuses)."""
        ps = self.psv.page_size
        Lp = r.prompt_len
        _, _, bt_a, lo = self._arrays(COHORT_MAIN)
        loc = r.slot - lo
        if r.rid in self._spec_primed:
            # The bucketed admission pass already primed the draft tree
            # through a mirrored draft-cohort group launch — only the
            # resume catch-up below remains.
            self._spec_primed.discard(r.rid)
        else:
            fn = self._spec_prefill_fn(Lp)
            self._key, sub = jax.random.split(self._key)
            _, _, self.caches_draft = fn(
                self.params_draft, self.caches_draft,
                jnp.asarray(r.prompt[None]),
                jnp.asarray(r.pages[:-(-Lp // ps)], jnp.int32),
                jnp.int32(loc), sub)
        # Resume catch-up: feed each parked token at its position through
        # the draft program (single active row, garbage-masked peers —
        # the _replay pattern), outputs ignored. No state snapshots
        # needed: speculation is attention-only.
        size = bt_a.shape[0]
        no_poison = jnp.zeros((size,), jnp.bool_)
        for p in range(Lp, Lp + len(r.out) - 1):
            tok_v = np.zeros((size,), np.int32)
            pos_v = np.zeros((size,), np.int32)
            bt = np.full_like(bt_a, PG.GARBAGE_PAGE)
            tok_v[loc] = r.out[p - Lp]
            pos_v[loc] = p
            bt[loc] = bt_a[loc]
            self._key, sub = jax.random.split(self._key)
            _, _, self.caches_draft = self._draft_decode_fn()(
                self.params_draft, self.caches_draft, jnp.asarray(tok_v),
                jnp.asarray(pos_v), jnp.asarray(bt), no_poison, sub)

    def _start(self, r: Request,
               pre: Optional[Tuple[int, bool]] = None) -> bool:
        """Bring an admitted request onto its slot: link its block table,
        consume the bucketed-prefill result planned for it (``pre``) or
        run the stage-1 prefill itself (full / suffix / skipped when the
        radix hit covers the whole prompt), and for resumed requests
        replay the parked generated positions. Returns False when a fault
        guard FAILED the request (admission rolled back: slot and pages
        already released). The device-boundary prompt guard ran in
        ``_plan_prefills`` — every request reaching here has in-vocab
        tokens."""
        ps = self.psv.page_size
        ctx = r.n_shared * ps
        Lp = r.prompt_len
        resumed = bool(r.out)
        tok_a, pos_a, bt_a, lo = self._arrays(r.cohort)
        row = bt_a[r.slot - lo]
        row[:] = PG.GARBAGE_PAGE
        row[:len(r.pages)] = r.pages
        # hit_tokens counts PROMPT tokens served from shared pages on FRESH
        # admissions only (a fresh match is prompt-only by the _match_cap);
        # a preemption resume re-linking its own donation is real savings
        # too but a different phenomenon — tracked under resume_hit_tokens
        # so hit_rate stays "prompt prefill work avoided by sharing".
        if resumed:
            self.counters["resume_hit_tokens"] += ctx
        else:
            self.counters["hit_tokens"] += ctx
            if ctx:
                self.counters["prefix_hits"] += 1
        if ctx < Lp:
            self.telemetry.span_event(
                r.rid, PREFILL, self.step_count,
                kind="full" if ctx == 0 else "suffix",
                hit_tokens=ctx, tokens=Lp - ctx, batched=pre is not None)
            if pre is not None:
                tok0, ok = pre
                self.counters["prefill_tokens"] += Lp - ctx
                self.counters["suffix_prefills" if ctx
                              else "full_prefills"] += 1
                self.counters["bucket_prefills"] += 1
            else:
                tok0, ok = self._run_prefill(r, ctx)
            if not ok:
                # The prefill may have scattered non-finite kv into the
                # request's pages before the guard was read — scrub.
                self._fail(r, NonFiniteLogitsError(
                    f"rid={r.rid}: non-finite logits/cache in prefill"),
                    scrub=True)
                return False
            if not resumed:
                r.out.append(tok0)
                self.telemetry.first_token(r.rid, self.step_count)
            elif self._exact:
                # Same program + same inputs as the original prefill: the
                # re-sampled first token must reproduce the parked one.
                assert tok0 == r.out[0], (tok0, r.out[0], r.rid)
        # Early donation: the prompt pages are complete now — concurrent
        # same-prefix requests admitted from the NEXT step on can share
        # them without waiting for this request to finish. (No-op for the
        # degraded cohort: its pages hold aggressive-plan bits.)
        self.sched.donate_prefilled(r, self.step_count)
        if resumed:
            if not self._replay(r, max(Lp, ctx)):
                self._fail(r, NonFiniteLogitsError(
                    f"rid={r.rid}: non-finite logits during decode replay"),
                    scrub=True)
                return False
        if self.spec_k:
            self._spec_prime(r)
        tok_a[r.slot - lo] = r.out[-1]
        pos_a[r.slot - lo] = r.pos
        return True

    def _finish(self, r: Request) -> None:
        slot = r.slot
        self.sched.finish(r, self.step_count)
        self._clear_slot(slot)
        self.results[r.rid] = np.asarray(r.out, np.int32)

    def _plan_prefills(self, admitted: List[Request]
                       ) -> Dict[int, Tuple[int, bool]]:
        """Pass 1 of admission: vocab-guard every admitted request, then
        pack the bucket-eligible prefills into (cohort, bucket) groups and
        launch each group ONCE. Returns rid -> (first token, finite-ok)
        for every request whose prefill ran batched; pass 2 (``_start``)
        consumes those instead of launching per request.

        Eligibility: the ladder is on, the request still has suffix
        tokens to compute (a full-prompt radix cover skips prefill
        entirely), and a rung holds the SUFFIX length. Radix-hit rows
        ride the same launch as cold rows through the ctx-aware bucket
        program (``_bucket_ctx_pages``): each row carries its own ctx
        pages + ctx length, cold rows pass zero ctx. Resumed re-prefills
        qualify too: the padded batched forward is bit-equal to the exact
        program, so the resume bit-identity assert still holds."""
        pre: Dict[int, Tuple[int, bool]] = {}
        ps = self.psv.page_size
        vocab = self.ms.cfg.vocab_size
        groups: Dict[Tuple[str, int], List[Request]] = {}
        for r in admitted:
            # Device-boundary prompt guard: submit-time validation ran,
            # but the prompt may have been corrupted since (the
            # poisoned-prompt chaos kind models a tokenizer/host bug). An
            # out-of-vocab id would index the embedding out of range —
            # fail the request, not the engine (and never launch a batch
            # holding it).
            if (r.prompt < 0).any() or (r.prompt >= vocab).any():
                self._fail(r, PoisonedPromptError(
                    f"rid={r.rid}: prompt token ids outside [0, {vocab}) "
                    f"at admission (min={int(r.prompt.min())}, "
                    f"max={int(r.prompt.max())})"), scrub=False)
                continue
            if not self._buckets:
                continue
            Ls = r.prompt_len - r.n_shared * ps
            if Ls <= 0:
                continue   # radix cover reaches the prompt: replay only
            b = BK.bucket_for(Ls, self._buckets)
            if b is not None:
                groups.setdefault((r.cohort, b), []).append(r)
        for (cohort, b), grp in sorted(groups.items()):
            pre.update(self._launch_bucket(cohort, b, grp))
        return pre

    def _launch_bucket(self, cohort: str, bucket: int, grp: List[Request]
                       ) -> Dict[int, Tuple[int, bool]]:
        """One bucket group: right-pad each row's SUFFIX (the full prompt
        when cold) to ``bucket``, launch chunks of the program's fixed row
        count (short chunks pad with inert rows: zero prompts, all-garbage
        page ids), slice each row's logits at its true length, and mask
        the page scatter so pad rows and pad pages write nothing. Under a
        ctx-aware program radix-hit rows additionally carry their matched
        ctx pages (garbage-padded to the uniform width) and ctx length."""
        ps = self.psv.page_size
        cohort_slots = self.n_main if cohort == COHORT_MAIN else self.n_deg
        rows = BK.rows_for_bucket(bucket, cohort_slots,
                                  self.psv.prefill_token_budget)
        ctx_pages = self._bucket_ctx_pages(cohort)
        fn = self._bucket_prefill_fn(bucket, rows, cohort)
        # Speculative mirror: the SAME group through the draft-plan
        # program warms the draft tree (quality-only — outputs ignored,
        # the trees are independent, and _spec_prime skips its own full
        # prefill for rids primed here). Radix-HIT rows are masked inert
        # in the mirror and NOT marked primed: the draft tree needs the
        # full prompt (its kv has no radix representation), so
        # _spec_prime runs their full-prompt draft prefill instead.
        draft_fn = (self._bucket_prefill_fn(bucket, rows,
                                            SP.COHORT_SPEC_DRAFT)
                    if self.spec_k and cohort == COHORT_MAIN else None)
        caches = self._get_caches(cohort)
        n_pg = bucket // ps
        out: Dict[int, Tuple[int, bool]] = {}
        for i0 in range(0, len(grp), rows):
            chunk = grp[i0:i0 + rows]
            prompts = np.zeros((rows, bucket), np.int32)
            true_lens = np.ones((rows,), np.int32)
            page_ids = np.full((rows, n_pg), PG.GARBAGE_PAGE, np.int32)
            ctx_ids = np.full((rows, ctx_pages), PG.GARBAGE_PAGE, np.int32)
            ctx_lens = np.zeros((rows,), np.int32)
            for i, r in enumerate(chunk):
                m = r.n_shared
                Ls = r.prompt_len - m * ps
                prompts[i, :Ls] = r.prompt[m * ps:]
                true_lens[i] = Ls
                npg = -(-r.prompt_len // ps)
                page_ids[i, :npg - m] = r.pages[m:npg]
                if m:
                    assert ctx_pages, (cohort, m)
                    ctx_ids[i, :m] = r.pages[:m]
                    ctx_lens[i] = m * ps
            self._key, sub = jax.random.split(self._key)
            if draft_fn is not None:
                hit = ctx_lens > 0
                d_prompts = np.where(hit[:, None], 0, prompts)
                d_lens = np.where(hit, 1, true_lens).astype(np.int32)
                d_pages = np.where(hit[:, None], PG.GARBAGE_PAGE,
                                   page_ids).astype(np.int32)
                _, _, self.caches_draft = draft_fn(
                    self.params_draft, self.caches_draft,
                    jnp.asarray(d_prompts), jnp.asarray(d_lens),
                    jnp.asarray(d_pages), sub)
            args = [jnp.asarray(prompts), jnp.asarray(true_lens),
                    jnp.asarray(page_ids)]
            if ctx_pages:
                args += [jnp.asarray(ctx_ids), jnp.asarray(ctx_lens)]
            tok0, ok, caches = fn(
                self._model(cohort)[0], caches, *args, sub)
            tok0, ok = np.asarray(tok0), np.asarray(ok)
            for i, r in enumerate(chunk):
                out[r.rid] = (int(tok0[i]), bool(ok[i]))
                if draft_fn is not None and not r.n_shared:
                    self._spec_primed.add(r.rid)
            self.counters["bucket_groups"] += 1
            self.counters["pad_tokens"] += rows * bucket - sum(
                r.prompt_len - r.n_shared * ps for r in chunk)
        self._set_caches(cohort, caches)
        return out

    def _admit(self, *, count_blocked: bool) -> None:
        degrade = (self.psv.degrade_delta
                   and self.sched.n_queued >= self.psv.degrade_queue_depth)
        admitted = self.sched.admit(self.step_count,
                                    count_blocked=count_blocked,
                                    degrade=degrade)
        pre = self._plan_prefills(admitted)
        for r in admitted:
            if r.status in TERMINAL_STATES:
                continue          # failed by the pass-1 vocab guard
            if r.cohort == COHORT_DEGRADED and not r.preemptions:
                self.counters["degraded_admissions"] += 1
            if not self._start(r, pre.get(r.rid)):
                continue
            # "admitted" counts requests that SURVIVED admission (slot
            # linked, prefill guards passed) — a request failed by a guard
            # inside _start counts under "failed" only.
            self.counters["admitted"] += 1
            if r.done():      # max_new == 1 (or instant EOS) on prefill
                self._finish(r)
            else:
                self.telemetry.span_event(r.rid, DECODE, self.step_count)

    def _decode_cohort(self, cohort: str) -> None:
        tok_a, pos_a, bt_a, lo = self._arrays(cohort)
        size = tok_a.shape[0]
        running = {s: r for s, r in self.sched.running.items()
                   if lo <= s < lo + size}
        if not running:
            return
        poison = np.zeros((size,), bool)
        for s in self._poison_slots:
            if lo <= s < lo + size:
                poison[s - lo] = True
        self._key, sub = jax.random.split(self._key)
        prof = (jax.profiler.StepTraceAnnotation(
                    f"paged_decode_{cohort}", step_num=self.step_count)
                if self.psv.profile_decode else contextlib.nullcontext())
        with prof:
            nxt, ok, caches = self._decode_fn(cohort)(
                self._model(cohort)[0], self._get_caches(cohort),
                jnp.asarray(tok_a), jnp.asarray(pos_a), jnp.asarray(bt_a),
                jnp.asarray(poison), sub)
        self._set_caches(cohort, caches)
        nxt = np.asarray(nxt)
        ok = np.asarray(ok)
        for slot, r in sorted(running.items()):
            loc = slot - lo
            if not bool(ok[loc]):
                # Non-finite logits on this row only: the decode step wrote
                # this slot's kv from finite inputs EXCEPT possibly under
                # real numeric poison, so scrub its private pages on the
                # way out; every other row is untouched (row independence).
                self._fail(r, NonFiniteLogitsError(
                    f"rid={r.rid}: non-finite logits in decode at step "
                    f"{self.step_count} (slot {slot})"),
                    scrub=True)
                continue
            r.out.append(int(nxt[loc]))
            tok_a[loc] = nxt[loc]
            pos_a[loc] += 1
            self.counters["decoded"] += 1
            if r.done():
                self._finish(r)

    def _rewind_pages(self, pairs: List[Tuple[int, int]]) -> None:
        """Un-write rejected speculative positions in BOTH cache trees.
        Fixed shape: at most ``n_main * spec_k`` positions can reject per
        step, padded with ``(GARBAGE_PAGE, 0)`` (paged_cache.rewind_tokens)
        so one compiled program per tree serves every episode."""
        cap = self.n_main * self.spec_k
        assert len(pairs) <= cap, (len(pairs), cap)
        pages = np.zeros((cap,), np.int32)
        offs = np.zeros((cap,), np.int32)
        for i, (p, o) in enumerate(pairs):
            pages[i], offs[i] = p, o
        rewind = self._programs.get(
            SP.COHORT_SPEC_VERIFY, "rewind", cap,
            lambda: jax.jit(PG.rewind_tokens, donate_argnums=(0,)))
        pg, of = jnp.asarray(pages), jnp.asarray(offs)
        self.caches = rewind(self.caches, pg, of)
        self.caches_draft = rewind(self.caches_draft, pg, of)

    def _decode_spec(self) -> None:
        """Speculative main-cohort step: ONE fused ``spec_k``-step draft
        episode launch at the aggressive plan, ONE full-depth verify
        launch at batch
        ``n_main * (spec_k + 1)``, host-side acceptance, then an un-write
        of every rejected position (serve.speculative has the math and
        the soundness argument). Replaces ``_decode_cohort(COHORT_MAIN)``
        when spec_k > 0; greedy streams are bit-identical to it because
        every committed token is a full-depth argmax over an
        exactly-committed history computed by the same decode body."""
        tok_a, pos_a, bt_a, lo = self._arrays(COHORT_MAIN)
        size = tok_a.shape[0]
        running = {s: r for s, r in self.sched.running.items()
                   if lo <= s < lo + size}
        if not running:
            return
        k = self.spec_k
        remaining = np.full((size,), -1, np.int64)
        for s, r in running.items():
            remaining[s - lo] = r.max_new - len(r.out)
        poison = np.zeros((size,), bool)
        for s in self._poison_slots:
            if lo <= s < lo + size:
                poison[s - lo] = True
        # One fused launch runs the whole episode: k greedy draft
        # proposals per slot at the aggressive plan (each internal step
        # feeds the previous proposal at the next position), the probe-
        # row packing, and every slot's k+1 rows through ONE regular
        # full-depth decode (launches-per-verify == 1 — the
        # spec-structural gate). make_spec_step_fn is the device-side
        # twin of speculative.build_draft_step/build_verify_batch.
        # Draft rows are never poisoned — poison targets the slot's
        # COMMITTED stream, which only the verify rows can move.
        self._key, sub = jax.random.split(self._key)
        prof = (jax.profiler.StepTraceAnnotation(
                    "paged_decode_spec_step", step_num=self.step_count)
                if self.psv.profile_decode else contextlib.nullcontext())
        with prof:
            d, yhat, ok, self.caches_draft, self.caches = self._spec_step(
                self.params_draft, self.params, self.caches_draft,
                self.caches, jnp.asarray(tok_a), jnp.asarray(pos_a),
                jnp.asarray(bt_a), jnp.asarray(poison),
                jnp.asarray(remaining.astype(np.int32)), sub)
        drafts = np.asarray(d)
        self.counters["draft_steps"] += k
        self.counters["verify_steps"] += 1
        yhat = np.asarray(yhat).reshape(size, k + 1)
        okm = np.asarray(ok).reshape(size, k + 1)
        zero_pairs: List[Tuple[int, int]] = []
        for slot, r in sorted(running.items()):
            loc = slot - lo
            rem = int(remaining[loc])
            j_hi = min(k, rem)
            if not okm[loc, :j_hi + 1].all():
                # Any live probe row non-finite fails the slot (the
                # non-spec engine's containment semantics: a poisoned
                # slot emits no token); peers are untouched by row
                # independence. Scrub covers the draft tree too.
                self._fail(r, NonFiniteLogitsError(
                    f"rid={r.rid}: non-finite logits in speculative "
                    f"verify at step {self.step_count} (slot {slot})"),
                    scrub=True)
                continue
            p0 = int(pos_a[loc])
            a_max = min(k, rem - 1)
            a = SP.accept_length(drafts[:, loc], yhat[loc], a_max)
            self.counters["spec_accepted"] += a
            self.counters["spec_rejected"] += a_max - a
            committed = 0
            for t in SP.commit_tokens(drafts[:, loc], yhat[loc], a):
                r.out.append(t)
                committed += 1
                self.counters["decoded"] += 1
                if r.done():     # EOS can cut inside the accepted run
                    break
            self.telemetry.observe("spec_accept", committed)
            self.telemetry.spec_episode(self.step_count, slot, r.rid,
                                        probed=a_max, accepted=a,
                                        committed=committed)
            if r.done():
                self._finish(r)
                continue
            tok_a[loc] = r.out[-1]
            pos_a[loc] = r.pos
            start, stop = SP.stale_span(p0, a, j_hi)
            if start < stop:
                zero_pairs += PG.rewind_plan(
                    r.pages, r.n_shared, start, stop,
                    self.psv.page_size)[0]
        if zero_pairs:
            self._rewind_pages(zero_pairs)
            self.counters["spec_rewound"] += len(zero_pairs)

    def _step_gauges(self, hit0: int, faults0: Dict[str, int]) -> None:
        """Per-step gauge samples, taken AFTER the step's work: queue
        depth, pool live/free/refcount-shared pages, per-step radix hit
        tokens (fresh + resume), per-cohort slot occupancy, and faults by
        kind (only steps where a kind fired emit a sample). All pure host
        reads — no device work."""
        tel, sc = self.telemetry, self.step_count
        tel.gauge("queue_depth", sc, self.sched.n_queued)
        tel.gauge("pages_live", sc, self.pool.live)
        tel.gauge("pages_free", sc, self.pool.n_free)
        tel.gauge("pages_shared", sc, self.pool.shared)
        tel.gauge("hit_tokens_step", sc,
                  tel.counters["hit_tokens"]
                  + tel.counters["resume_hit_tokens"] - hit0)
        n_run_main = sum(1 for s in self.sched.running if s < self.n_main)
        tel.gauge(f"slots_live/{COHORT_MAIN}", sc, n_run_main)
        if self.n_deg:
            tel.gauge(f"slots_live/{COHORT_DEGRADED}", sc,
                      self.sched.n_running - n_run_main)
        for kind, n in tel.fault_counts.items():
            d = n - faults0.get(kind, 0)
            if d:
                tel.gauge(f"faults/{kind}", sc, d)

    def step(self) -> Dict[str, int]:
        """One engine iteration: chaos injection (when armed) -> deadline
        expiry -> admission+prefill (with blocked-head preemption when
        enabled) -> block-table validation -> one decode program per active
        cohort. Returns the step's lifecycle event counts — computed as
        telemetry counter DELTAS over the step, so there is exactly one
        increment site per event and the per-step view can never drift
        from the monotone totals."""
        tel = self.telemetry
        before = {k: tel.counters[k] for k in self.STEP_STAT_KEYS}
        hit0 = tel.counters["hit_tokens"] + tel.counters["resume_hit_tokens"]
        faults0 = dict(tel.fault_counts)
        if self._plan is not None:
            self._inject()
        self._expire_pass()
        self._admit(count_blocked=True)
        if self.sched.should_preempt():
            _victim, slot = self.sched.preempt_youngest(self.step_count)
            self._clear_slot(slot)
            # The freed pages/slot may unblock the head immediately.
            self._admit(count_blocked=False)
        self._validate_block_tables()
        if self.spec_k:
            self._decode_spec()
        else:
            self._decode_cohort(COHORT_MAIN)
        if self.n_deg:
            self._decode_cohort(COHORT_DEGRADED)
        self._poison_slots.clear()
        self.pool.check_balance()
        if self.prefix is not None:
            self.prefix.check_locks()
        self._step_gauges(hit0, faults0)
        tel.mark_step(self.step_count)
        self.step_count += 1
        stats = {k: tel.counters[k] - before[k] for k in self.STEP_STAT_KEYS}
        stats["live_pages"] = self.pool.live
        return stats

    def drain(self) -> Dict[int, np.ndarray]:
        """Step until every submitted request reached a TERMINAL state;
        returns {rid: generated tokens}. Backwards-compatible: the dict
        maps every rid (including FAILED/CANCELLED/EXPIRED, whose value is
        the partial output produced before termination) — per-request
        status is ``engine.request(rid).state`` and the typed error
        ``engine.request(rid).error``. Cancelling or expiring mid-flight
        can therefore never hang the drain: terminal requests leave the
        queue/running sets the step they terminate."""
        while self.sched.n_queued or self.sched.n_running:
            self.step()
        return dict(self.results)

    @property
    def occupancy(self) -> float:
        """Fraction of allocatable cache pages currently live."""
        return self.pool.live / max(self.psv.n_pages - 1, 1)

    def request(self, rid: int) -> Request:
        return self._requests[rid]

    # -- telemetry exporters -------------------------------------------
    def metrics_snapshot(self) -> Dict[str, Any]:
        """JSON-able metrics snapshot: counters, last-value gauges,
        histograms, compile events, fault counts, request-state census,
        span-derived latency (step percentiles + ``wall`` ms annotations),
        prefix hit rate, pool accounting, and pool-occupancy series stats.
        Everything outside ``wall*`` keys is a pure function of the
        step-denominated event stream (same-seed runs snapshot
        identically once wall fields are stripped)."""
        snap = self.telemetry.snapshot(step=self.step_count)
        snap["pool"] = {
            "allocated_total": self.pool.allocated_total,
            "freed_total": self.pool.freed_total,
            "shared_total": self.pool.shared_total,
            "alloc_faults": self.pool.alloc_faults,
            "live": self.pool.live,
        }
        cap = max(self.psv.n_pages - 1, 1)
        series = self.telemetry.gauge_series.get("pages_live", [])
        if series:
            vals = [v for _, v in series]
            snap["occupancy"] = {
                "mean": round(sum(vals) / len(vals) / cap, 3),
                "max": round(max(vals) / cap, 3),
            }
        snap["preemptions"] = self.sched.preemptions_total
        if self.spec_k:
            c = self.telemetry.counters
            probed = c["spec_accepted"] + c["spec_rejected"]
            # One histogram observation per slot per verify = one episode;
            # its mean is committed tokens per full-depth verification of
            # a slot — the speedup lever (> 1 means each full-depth pass
            # commits more than a one-token step would).
            h = self.telemetry.hists.get("spec_accept")
            snap["spec"] = {
                "k": self.spec_k,
                "draft_eff_depth": self.ms_draft.effective_depth,
                "accept_per_verify": round(h.sum / h.count, 3)
                                     if h and h.count else 0.0,
                "accept_rate": round(c["spec_accepted"] / probed, 3)
                               if probed else 0.0,
            }
        return snap

    def metrics_text(self) -> str:
        """Prometheus-style text exposition of the scalar channels."""
        return self.telemetry.prom_text()

    def dump_trace(self, path: str) -> str:
        """Write the Chrome/Perfetto ``trace_event`` JSON for this run
        (repro.serve.trace). Needs spans/gauge series, so the engine must
        run with ``telemetry=True`` (the default)."""
        return write_trace(self.telemetry, path, n_slots=self.psv.n_slots)


# ---------------------------------------------------------------------------
# Sharded wrappers (mesh execution + dry-run lowering)
# ---------------------------------------------------------------------------

def _tree_shardings(mesh, pspecs):
    """PartitionSpec tree -> NamedSharding tree (P is a tuple: need is_leaf)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def cache_pspecs(ms: T.ModelStructure, *, batch: int, sv: ServeConfig,
                 pc: ParallelContext, shard_batch: bool = True):
    """(abstract, pspec) for the global cache; batch sharded over dp when
    ``shard_batch`` (batch==1 long-context cells replicate it)."""
    abs_, ps_ = T.cache_meta(ms, batch=batch, max_len=sv.max_len,
                             kv_mode=sv.kv_mode, dtype=sv.cache_dtype)
    dp = tuple(pc.dp_axes) if pc.dp_axes else (None,)
    dp_ax = (dp if len(dp) > 1 else dp[0]) if shard_batch else None

    def add_dp(path, spec):
        # Shard the batch axis over dp: axis 1 for per-layer entries
        # ([count, batch, ...]), axis 2 for stacked pair entries
        # ([count, 2, batch, ...]) — see T.cache_batch_axis.
        parts = list(spec)
        parts[T.cache_batch_axis(path[-1].key)] = dp_ax
        return P(*parts)

    ps2 = jax.tree_util.tree_map_with_path(
        add_dp, ps_, is_leaf=lambda x: isinstance(x, P))
    return abs_, ps2


def make_sharded_serve_step(ms: T.ModelStructure, mesh, sv: ServeConfig,
                            *, batch: int, shard_batch: bool = True,
                            paged: Optional[PagedServeConfig] = None):
    """jit(shard_map(serve_step)) + its in/out specs, for execution and the
    decode-shape dry-run.

    ``paged`` threads the continuous-batching engine's pool through the
    same wrapper: the local step becomes the paged decode (params, caches,
    tok, pos, block_tables, poison, key) -> (next_tok, ok, caches) with
    the pool's pspecs from ``paged_cache_meta`` (kv-head axis over
    "model", everything else replicated) and tok/pos/block tables/poison
    replicated — host-side scheduling is tp-agnostic, so the ONLY sharded
    state is the pool itself; the finite flag ``ok`` is pmax-reduced over
    tp inside the step so its replicated out-spec holds. ``sv`` may be
    None in that mode; ``batch`` is the slot count.
    """
    if paged is not None:
        pc = make_context(mesh, sp=False)
        local = make_paged_decode_fn(ms, pc, paged)
        p_specs = T.param_pspecs(ms)
        c_abs, c_specs = PG.paged_cache_meta(
            ms, n_slots=batch, n_pages=paged.n_pages,
            page_size=paged.page_size, dtype=paged.cache_dtype)
        wrapped = jax.shard_map(
            local, mesh=mesh,
            in_specs=(p_specs, c_specs, P(), P(), P(), P(), P()),
            out_specs=(P(), P(), c_specs),
            check_vma=False)
        return jax.jit(wrapped, donate_argnums=(1,)), c_abs, c_specs, pc
    pc = make_context(mesh, sp=False)
    local = make_serve_step(ms, pc, sv)
    p_specs = T.param_pspecs(ms)
    c_abs, c_specs = cache_pspecs(ms, batch=batch, sv=sv, pc=pc,
                                  shard_batch=shard_batch)
    dp = tuple(pc.dp_axes) if pc.dp_axes else (None,)
    dp_ax = (dp if len(dp) > 1 else dp[0]) if shard_batch else None
    tok_spec = P(dp_ax)
    wrapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(p_specs, tok_spec, c_specs, P(), P()),
        out_specs=(tok_spec, c_specs),
        check_vma=False)
    return jax.jit(wrapped, donate_argnums=(2,)), c_abs, c_specs, pc


def make_sharded_prefill(ms: T.ModelStructure, mesh, sv: ServeConfig,
                         *, batch: int, prompt_len: int, sp: bool = True,
                         paged: Optional[PagedServeConfig] = None,
                         paged_slots: Optional[int] = None,
                         bucket_rows: Optional[int] = None,
                         bucket_ctx_pages: int = 0,
                         suffix_ctx_pages: Optional[int] = None):
    """jit(shard_map(prefill)) for the ring cache (default), or — with
    ``paged`` — the engine's exact-length prefill + page scatter: the
    forward runs replicated over the sequence (sp off: prompt lengths are
    exact, not tp-multiples), each rank scatters its LOCAL kv-head shard
    of the emitted pages into its pool shard, and page ids/slot stay
    host-side and tp-agnostic. ``paged_slots`` overrides the cache tree's
    slot count (cohort-partitioned engines build per-cohort trees).
    ``bucket_rows``: build the BUCKETED batched prefill instead —
    ``prompt_len`` is the bucket width and the program takes
    ``[bucket_rows, prompt_len]`` right-padded prompts plus per-row true
    lengths and page-id rows; ``bucket_ctx_pages > 0`` adds the per-row
    ctx operands (radix-hit rows ride the bucket — the ctx gather and
    per-rank fold run inside shard_map over each rank's pool shard).
    ``suffix_ctx_pages``: build the exact-shape SUFFIX prefill instead —
    ``prompt_len`` is the suffix length. Every non-tree operand is
    replicated (P()), so the spec count just follows the local program's
    arity. Returns (fn, cache_pspecs, pc)."""
    if paged is not None:
        pc = make_context(mesh, sp=False)
        if suffix_ctx_pages is not None:
            local = make_paged_suffix_prefill_fn(
                ms, pc, paged, suffix_ctx_pages, prompt_len)
            n_rep = 5   # suffix, ctx_ids, sfx_ids, slot, key
        elif bucket_rows is not None:
            local = make_paged_bucket_prefill_fn(
                ms, pc, paged, prompt_len, bucket_rows, bucket_ctx_pages)
            # prompts, true_lens, page_ids, [ctx_ids, ctx_lens,] key
            n_rep = 4 + (2 if bucket_ctx_pages else 0)
        else:
            local = make_paged_prefill_fn(ms, pc, paged, prompt_len)
            n_rep = 4   # prompt, page_ids, slot, key
        p_specs = T.param_pspecs(ms)
        _, c_specs = PG.paged_cache_meta(
            ms, n_slots=paged_slots or paged.n_slots, n_pages=paged.n_pages,
            page_size=paged.page_size, dtype=paged.cache_dtype)
        wrapped = jax.shard_map(
            local, mesh=mesh,
            in_specs=(p_specs, c_specs) + (P(),) * n_rep,
            out_specs=(P(), P(), c_specs),
            check_vma=False)
        return jax.jit(wrapped, donate_argnums=(1,)), c_specs, pc
    pc = make_context(mesh, sp=sp)
    local = make_prefill(ms, pc, sv)
    p_specs = T.param_pspecs(ms)
    _, c_specs = cache_pspecs(ms, batch=batch, sv=sv, pc=pc)
    dp = tuple(pc.dp_axes) if pc.dp_axes else (None,)
    dp_ax = dp if len(dp) > 1 else dp[0]
    in_specs = [p_specs, P(dp_ax, None)]
    # Extras ride positionally after ``tokens``: a [B, prefix_len, D]
    # patch-embedding prefix (vlm) and/or [B, enc_seq, D] encoder frames
    # (encdec) — both [B, S, D] with only the batch axis dp-sharded, so
    # every extra takes the same spec.
    n_extras = int(bool(ms.cfg.prefix_len)) + int(bool(ms.enc_segments))
    in_specs.extend([P(dp_ax, None, None)] * n_extras)

    def local_n(params, tokens, *extras):
        prefix = frames = None
        i = 0
        if ms.cfg.prefix_len:
            prefix = extras[i]; i += 1
        if ms.enc_segments:
            frames = extras[i]; i += 1
        return local(params, tokens, prefix, frames)

    wrapped = jax.shard_map(
        local_n, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(dp_ax, "model"), c_specs),
        check_vma=False)
    return jax.jit(wrapped), c_specs, pc


def make_sharded_generate(ms: T.ModelStructure, mesh, sv: ServeConfig,
                          *, batch: int, prompt_len: int):
    """Build the one-shot sharded generation loop ONCE (prefill + serve
    step jits are per-instance, so reusing the returned closure is what
    makes a warm call actually warm the next one). Returns
    ``gen(params, prompts [batch, prompt_len], n_new, key=None) ->
    [batch, n_new] np.int32``.

    The prefill runs without sequence parallelism so the forward matches
    the engine's exact-length paged prefill shape-for-shape (SP would need
    prompt_len % tp == 0 and regroup the sequence reductions).
    """
    assert sv.temperature == 0.0, "sharded generation is the greedy reference"
    # Fail fast rather than silently dropping the prefix/frames extras the
    # ring prefill would expect positionally (transformer.forward_full runs
    # prefix-LM archs WITHOUT their prefix when prefix_embed is None).
    assert not ms.cfg.prefix_len and not ms.enc_segments, (
        f"{ms.cfg.name}: sharded one-shot generation does not take "
        "prefix/encoder extras yet")
    pre, _, _ = make_sharded_prefill(ms, mesh, sv, batch=batch,
                                     prompt_len=prompt_len, sp=False)
    step, _, _, _ = make_sharded_serve_step(ms, mesh, sv, batch=batch,
                                            shard_batch=False)

    def gen(params, prompts, n_new: int, key=None) -> np.ndarray:
        prompts = jnp.asarray(prompts, jnp.int32)
        assert prompts.shape == (batch, prompt_len), prompts.shape
        logits, caches = pre(params, prompts)
        # Gathered full-vocab logits: argmax's first-max tie-break equals
        # vocab_parallel_argmax's smallest-global-id rule.
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks = [np.asarray(tok)]
        key_ = key if key is not None else jax.random.PRNGKey(0)
        for i in range(n_new - 1):
            key_, sub = jax.random.split(key_)
            tok, caches = step(params, tok, caches, jnp.int32(prompt_len + i),
                               sub)
            toks.append(np.asarray(tok))
        return np.stack(toks, axis=1).astype(np.int32)

    return gen


def sharded_generate(params, prompts, n_new: int, *, ms: T.ModelStructure,
                     mesh, sv: ServeConfig, key=None) -> np.ndarray:
    """One-shot greedy generation under shard_map (ring cache, host decode
    loop): the tp > 1 reference stream the sharded paged engine is gated
    against. ``prompts``: [B, S] token ids. Returns [B, n_new] np.int32.
    One-off convenience over ``make_sharded_generate`` — compiles fresh
    programs per call; loops should build the factory once."""
    prompts = jnp.asarray(prompts, jnp.int32)
    B, S = prompts.shape
    return make_sharded_generate(ms, mesh, sv, batch=B, prompt_len=S)(
        params, prompts, n_new, key)
