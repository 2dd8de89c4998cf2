"""Paged pair-KV cache pool for the continuous-batching engine.

One-shot ``generate()`` gives every request a contiguous ring cache of
``max_len`` slots for its whole life — fine for a fixed batch, hopeless for
serving: a short request strands the memory of a long one and nothing can be
admitted until the whole batch drains. The paged pool instead carves the
cache into fixed-size PAGES handed out from a free list; a request holds
exactly the pages its length needs and returns them the moment it finishes,
so requests of very different lengths share one cache allocation.

Layout: the pool keeps PR 1's stacked pair layout end to end. A fused LP
pair's k/v pool is ``[2, n_pages, Hkv, page_size, hd]`` (leading pair axis,
bare entry names), a per-layer entry is ``[n_pages, Hkv, page_size, hd]``
(indexed names ``k0``/``v0``) — the ring layout's ``[B, L, Hkv, hd]``
tail cut into pages that are head-major inside, so one (page, head) block
is a contiguous ``[page_size, hd]`` tile for the decode kernels.
``attention.seq_to_pages`` / ``pages_to_seq`` are the only conversions
between the two; every scatter and gather here goes through them. Both
halves of a pair live at
the SAME page indices of their own half of the leading axis, so one block
table serves the pair and homogeneous pairs still stream through one kernel
launch (``repro.kernels.decode_attention.decode_attention_pair_paged``).

Indirection: a block table ``[n_slots, pages_per_slot]`` maps each decode
slot's logical position ``t`` to ``(page, offset) = (bt[slot, t // ps],
t % ps)``. Page 0 is RESERVED as the garbage page: idle slots and the
unused tail of every block-table row point at it, so padded slots in the
fixed-shape decode batch write/read harmlessly without masking logic on
device. The free list never hands out page 0.

Mamba/RG-LRU state entries (``conv``/``h``) are O(1) per request and are
not paged — they stay slot-indexed with ``n_slots`` as the batch axis.
Cross-attention caches and non-causal ring kinds (sliding-window/chunked)
are not supported by the paged layout; ``validate_paged_support`` rejects
them up front.

Sharding (tp > 1): the pool shards over the model axis exactly like the
ring cache — the stored kv-head axis is cut when kv heads are sharded
(n_kv >= tp, so each rank's shard is ``[2, n_pages, Hkv/tp, page_size,
hd]``), replicated when n_kv < tp (ranks select their head in-kernel).
``paged_cache_meta`` derives the pspecs from the ring meta by the same
axis permutation as the shapes, so no new partition rules exist for
paged serving. Page ids, block tables and slot indices are host-side and
tp-agnostic — ``scatter_prefill``/decode writes run unchanged inside
shard_map on each rank's local shard.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.model import blocks as B
from repro.model import transformer as T
from repro.model.attention import pages_to_seq, seq_to_pages

PyTree = Any

#: Reserved garbage page: idle slots and unused block-table entries point here.
GARBAGE_PAGE = 0


def is_paged_entry(name: str) -> bool:
    """Self-attention k/v entries are paged (per-token length dim); state
    entries (conv/h) are slot-indexed; cross-attention (xk/xv) unsupported."""
    return name.rstrip("0123456789") in ("k", "v")


def pages_needed(prompt_len: int, max_new: int, page_size: int) -> int:
    """Pages a request holds for its whole life (prompt + all new tokens)."""
    return -(-(prompt_len + max_new) // page_size)


def validate_paged_support(ms: T.ModelStructure, max_len: int) -> None:
    """The paged layout covers plain causal attention caches + slot state.

    Rejects: encoder/cross-attention (whisper), prefix-LM (paligemma), and
    ring kinds whose cache is a reused window/chunk ring rather than one
    slot per absolute position (recurrentgemma's attn_local, llama4's
    attn_chunked) — paging a reused ring would need per-page eviction.

    TP: a kv-SHARDED pool (n_kv >= tp) cuts the stored head axis into
    equal per-rank shards, so ``n_kv`` must divide by ``tp`` — the padded
    hkv_global the ring cache tolerates would put phantom heads in the
    pool and the paged kernel's scalar-prefetch index maps would walk off
    the real heads. Reject it HERE with an actionable message instead of
    failing inside the kernel index map. Replicated kv (n_kv < tp) has no
    divisibility requirement: every rank holds all stored heads and
    selects in-kernel (kernels.decode_attention head_map).
    """
    cfg = ms.cfg
    if ms.enc_segments or cfg.enc_layers:
        raise ValueError(f"{cfg.name}: encoder/cross-attention caches are "
                         "not pageable")
    if cfg.prefix_len:
        raise ValueError(f"{cfg.name}: prefix-LM serving is not paged yet")
    dims = ms.dims
    if ms.tp > 1 and dims.kv_sharded and cfg.n_kv_heads % ms.tp:
        raise ValueError(
            f"{cfg.name}: n_kv_heads={cfg.n_kv_heads} does not divide by "
            f"tp={ms.tp}; the paged pool shards stored kv heads evenly over "
            "the model axis (the ring cache pads to "
            f"{dims.hkv_global} heads, but padded pool heads would desync "
            "the paged kernel's block-table index maps) — pick tp dividing "
            "n_kv_heads, or tp > n_kv_heads for replicated-kv selection")
    for seg in ms.segments:
        for spec in seg.group.specs:
            if spec.cross_attn:
                raise ValueError(f"{cfg.name}: cross-attention not pageable")
            m = spec.mixer
            if m.startswith("attn") and B.ring_len(cfg, m, max_len) != max_len:
                raise ValueError(
                    f"{cfg.name}: {m} reuses a ring of "
                    f"{B.ring_len(cfg, m, max_len)} < {max_len} slots; paged "
                    "layout requires one slot per absolute position")


def paged_cache_meta(ms: T.ModelStructure, *, n_slots: int, n_pages: int,
                     page_size: int, dtype=jnp.bfloat16):
    """(abstract, pspec) trees for the paged pool, mirroring the ring cache
    tree structure (same segment list, same entry names) with the ``[B, L,
    H, hd]`` tail of every paged entry replaced by the page layout
    ``[n_pages, H, page_size, hd]``.

    ``dtype`` plays the role of ``prefill``'s cache cast: every float entry
    of the ring meta (including the fp32 recurrent state) is stored at
    ``dtype`` so pool contents match what a ring cache holds after the
    prefill cast.
    """
    abs_, ps_ = T.cache_meta(ms, batch=n_slots, max_len=n_pages * page_size,
                             kv_mode="heads", dtype=dtype)

    def remap(seg_abs, seg_ps):
        na, np_ = {}, {}
        for name, a in seg_abs.items():
            ba = T.cache_batch_axis(name)  # [count, (2,) B, ...]
            dt = dtype if a.dtype in (jnp.float32, jnp.bfloat16) else a.dtype
            if is_paged_entry(name):
                # [count, (2,) B, L, H, hd] -> [count, (2,) n_pages, H, ps, hd]
                H, hd = a.shape[ba + 2:]
                shape = (*a.shape[:ba], n_pages, H, page_size, hd)
                spec = list(seg_ps[name])
                spec += [None] * (a.ndim - len(spec))
                spec = spec[:ba] + [spec[ba], spec[ba + 2], spec[ba + 1],
                                    spec[ba + 3]]
                na[name] = jax.ShapeDtypeStruct(shape, dt)
                np_[name] = P(*spec)
            else:
                na[name] = jax.ShapeDtypeStruct(a.shape, dt)
                np_[name] = seg_ps[name]
        return na, np_

    outs = [remap(a, p) for a, p in zip(abs_, ps_)]
    return [o[0] for o in outs], [o[1] for o in outs]


def init_paged_caches(ms: T.ModelStructure, *, n_slots: int, n_pages: int,
                      page_size: int, dtype=jnp.bfloat16) -> List[Dict]:
    abs_, _ = paged_cache_meta(ms, n_slots=n_slots, n_pages=n_pages,
                               page_size=page_size, dtype=dtype)
    return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abs_)


def gather_ctx(pool: List[Dict], page_ids) -> List[Dict]:
    """Gather a prefix's pages into per-segment CONTEXT kv trees for the
    suffix prefill (``forward_full(ctx_kv=..., start=n_pg * page_size)``).

    pool: the paged cache tree; page_ids: [n_pg] int32 pages covering the
    matched prefix in position order. Returns one tree per segment with the
    emitted-cache layer layout and a batch-1 length axis: stacked pair
    entries [count, 2, 1, n_pg * ps, Hkv, hd], per-layer entries
    [count, 1, n_pg * ps, Hkv, hd]. Slot-state entries (conv/h) have no kv
    to resume from and are rejected upstream (prefix sharing is
    attention-only).
    """
    out = []
    for seg in pool:
        nseg = {}
        for name, pv in seg.items():
            assert is_paged_entry(name), (
                f"{name}: prefix sharing requires attention-only caches")
            ba = T.cache_batch_axis(name)   # page axis of the pool entry
            g = jnp.take(pv, page_ids, axis=ba)   # [.., n_pg, H, ps, hd]
            nseg[name] = jnp.expand_dims(pages_to_seq(g, ba), ba)
        out.append(nseg)
    return out


def gather_ctx_rows(pool: List[Dict], page_ids) -> List[Dict]:
    """Per-row twin of ``gather_ctx`` for the bucketed radix-suffix path:
    gather EVERY row's ctx pages in one shot so prefix-hit and cold rows
    share a single ``[rows, bucket]`` prefill launch.

    pool: the paged cache tree; page_ids: [rows, n_ctx_pages] int32 — row
    i's first ``ctx_len_i / page_size`` entries are its matched prefix
    pages in position order, the rest (and every entry of a cold row) is
    ``GARBAGE_PAGE``. Returns one tree per segment with the emitted-cache
    layout and ``rows`` as the batch axis: stacked pair entries
    [count, 2, rows, n_ctx_pages * ps, Hkv, hd], per-layer entries
    [count, rows, n_ctx_pages * ps, Hkv, hd]. Garbage-directed positions
    gather the all-zero garbage page — finite junk the forward's per-row
    key rearrangement parks behind each row's causal horizon, where the
    pinned-tile chunked core treats it as exact-zero contribution (the
    same masked-no-op argument as bucket padding). Attention-only, like
    everything on the prefix path.
    """
    out = []
    for seg in pool:
        nseg = {}
        for name, pv in seg.items():
            assert is_paged_entry(name), (
                f"{name}: prefix sharing requires attention-only caches")
            ba = T.cache_batch_axis(name)   # page axis of the pool entry
            # [.., rows, n_pg, H, ps, hd]: rows becomes the batch axis in
            # place (no expand_dims — the row axis replaces batch-1).
            g = jnp.take(pv, page_ids, axis=ba)
            nseg[name] = pages_to_seq(g, ba + 1)
        out.append(nseg)
    return out


def scrub_pages(pool: List[Dict], page_ids, slot):
    """Zero a departing request's pages and its slot-state rows.

    Fault-containment path: when a request FAILS with possibly non-finite
    cache contents (NaN params/activations during its prefill or decode),
    its private pages go back to the free list — and a later holder would
    gather whatever bits were left there. Masking makes stale values
    *ignored* in the softmax, but NaN is absorbing through masked lanes in
    some kernel layouts, so the engine scrubs before freeing rather than
    trusting masks. ``page_ids`` is fixed-shape (padded with
    ``GARBAGE_PAGE`` — zeroing the garbage page is harmless by definition),
    so one compiled program serves every failure. ``slot`` additionally
    clears the non-paged recurrent-state entries (conv/h) at the slot.
    """
    out = []
    for seg in pool:
        nseg = {}
        for name, pv in seg.items():
            ba = T.cache_batch_axis(name)
            if is_paged_entry(name):
                z = jnp.zeros((), pv.dtype)
                if ba == 2:   # stacked pair entry [count, 2, n_pages, ...]
                    nseg[name] = pv.at[:, :, page_ids].set(z)
                else:         # per-layer entry [count, n_pages, ...]
                    nseg[name] = pv.at[:, page_ids].set(z)
            else:
                zs = (*pv.shape[:ba], 1, *pv.shape[ba + 1:])
                nseg[name] = lax.dynamic_update_slice_in_dim(
                    pv, jnp.zeros(zs, pv.dtype), slot, axis=ba)
        out.append(nseg)
    return out


def scatter_prefill(pool: List[Dict], seq: List[Dict], page_ids, slot):
    """Place one request's prefill caches into its pages / state slot.

    pool: the paged cache tree (list of per-segment dicts).
    seq:  a batch-1 ring cache tree from ``forward_full(emit_cache=True,
          max_len=n_scatter_pages * page_size)`` — i.e. the cache length is
          already a whole number of pages.
    page_ids: [n_scatter_pages] int32 — the FIRST ceil(prompt_len /
          page_size) pages the request owns (always <= its allocation,
          since it holds pages for prompt + max_new). Positions in the
          last page past the true prompt length receive garbage; that is
          safe because they stay masked (pos > horizon) until the decode
          loop overwrites each of them in turn.
    slot: scalar int32 decode slot (receives the non-paged state entries).
    """
    n_pg = page_ids.shape[0]
    out = []
    for pool_seg, seq_seg in zip(pool, seq):
        nseg = {}
        for name, pv in pool_seg.items():
            sv = seq_seg[name]
            ba = T.cache_batch_axis(name)
            if is_paged_entry(name):
                ps = pv.shape[ba + 2]
                s = jnp.squeeze(sv, axis=ba)   # drop B=1 -> length at ba
                s = seq_to_pages(s, ba, ps).astype(pv.dtype)
                if ba == 2:   # stacked pair entry [count, 2, n_pages, ...]
                    nseg[name] = pv.at[:, :, page_ids].set(s)
                else:         # per-layer entry [count, n_pages, ...]
                    nseg[name] = pv.at[:, page_ids].set(s)
            else:
                # Slot state: write the request's B=1 slice at its slot.
                nseg[name] = lax.dynamic_update_slice_in_dim(
                    pv, sv.astype(pv.dtype), slot, axis=ba)
        out.append(nseg)
    return out


def scatter_prefill_rows(pool: List[Dict], seq: List[Dict], page_ids):
    """Place a BUCKETED prefill batch's caches into each row's pages in
    one shot — the batched twin of ``scatter_prefill``.

    pool: the paged cache tree.
    seq:  a batch-``n_rows`` ring cache tree from the bucket forward
          (``forward_full(emit_cache=True, max_len=bucket)`` — the bucket
          is a whole number of pages).
    page_ids: [n_rows, n_pg] int32. Row i's first ``ceil(true_len_i /
          page_size)`` entries are its real pages; every PAD entry — the
          whole-page tail a short prompt does not reach, and every entry
          of an empty pad row — is ``GARBAGE_PAGE``. Garbage-directed
          chunks are ZEROED before the scatter, so (a) pad rows write
          nothing anywhere real, (b) the garbage page stays all-zero (its
          contract), and (c) the duplicate garbage indices are
          deterministic — every colliding write stores the same zeros.
          Positions in a row's LAST real page past its true length
          receive that row's junk-tail kv, exactly like the exact-length
          path's emit rounding: safe because they stay masked (pos >
          horizon) until decode overwrites each in turn.

    Bucketing is attention-only (the engine gates it on the same
    eligibility as prefix sharing), so there are no slot-state entries to
    place — a recurrent mixer's state would advance on pad positions with
    no way to mask the corruption.
    """
    n_rows, n_pg = page_ids.shape
    flat = page_ids.reshape(-1)                      # [n_rows * n_pg]
    valid = flat != GARBAGE_PAGE
    out = []
    for pool_seg, seq_seg in zip(pool, seq):
        nseg = {}
        for name, pv in pool_seg.items():
            assert is_paged_entry(name), (
                f"{name}: bucketed prefill requires attention-only caches")
            sv = seq_seg[name]
            ba = T.cache_batch_axis(name)            # rows at ba, len at ba+1
            ps = pv.shape[ba + 2]
            # [.., rows, n_pg, H, ps, hd] -> merge (rows, n_pg): adjacent.
            s = seq_to_pages(sv, ba + 1, ps)
            s = s.reshape(*s.shape[:ba], n_rows * n_pg, *s.shape[ba + 2:])
            mask = valid.reshape((1,) * ba + (n_rows * n_pg,)
                                 + (1,) * (s.ndim - ba - 1))
            s = jnp.where(mask, s, jnp.zeros((), s.dtype)).astype(pv.dtype)
            if ba == 2:   # stacked pair entry [count, 2, n_pages, ...]
                nseg[name] = pv.at[:, :, flat].set(s)
            else:         # per-layer entry [count, n_pages, ...]
                nseg[name] = pv.at[:, flat].set(s)
        out.append(nseg)
    return out


def rewind_tokens(pool: List[Dict], page_ids, offsets):
    """Un-write single token positions: zero ``(page_ids[i], offsets[i])``
    across every paged entry (both halves of a stacked pair at once).

    Speculative-decoding rewind path: rejected draft tokens left kv at
    positions past the slot's committed horizon. Those bits can never be
    *read* wrong — every decode launch scatters a row's kv before any row
    gathers, and per-row masks hide positions beyond each row's own
    ``pos`` — but the pool contract that pages hold only committed-token
    kv is what prefix sharing and the accounting audits lean on, so the
    engine restores it eagerly. Fixed-shape like ``scrub_pages``: pad the
    pair lists with ``(GARBAGE_PAGE, 0)`` (zeroing the garbage page is
    harmless by definition; duplicate pairs all write the same zero), so
    one compiled program serves every episode. Slot-state entries are left
    alone — speculation is attention-only (see serve.speculative).
    """
    out = []
    for seg in pool:
        nseg = {}
        for name, pv in seg.items():
            ba = T.cache_batch_axis(name)
            if is_paged_entry(name):
                z = jnp.zeros((), pv.dtype)
                if ba == 2:   # stacked pair entry [count, 2, n_pages, H, ps, hd]
                    nseg[name] = pv.at[:, :, page_ids, :, offsets].set(z)
                else:         # per-layer entry [count, n_pages, H, ps, hd]
                    nseg[name] = pv.at[:, page_ids, :, offsets].set(z)
            else:
                nseg[name] = pv
        out.append(nseg)
    return out


def rewind_plan(pages: List[int], n_shared: int, new_len: int, old_len: int,
                page_size: int) -> Tuple[List[Tuple[int, int]], List[int]]:
    """Host-side rewind bookkeeping: shrink a request's written horizon
    from ``old_len`` to ``new_len`` tokens.

    Returns ``(zero_pairs, free_pages)``:

    - ``zero_pairs``: the ``(page, offset)`` of every position in
      ``[new_len, old_len)`` — feed to ``rewind_tokens`` to un-write them.
    - ``free_pages``: the trailing pages left with NO live position — an
      allocator that extends page holdings on demand returns these via
      ``PagePool.free_rewound`` (which re-checks they are privately held).
      The engine's own allocator claims prompt + max_new pages up front
      and re-uses rewound positions for later commits, so it ignores this
      list; the distinction is exercised by the rewind property test.

    Radix-shared pages are read-only by refcount — a rewind may only
    un-write THIS request's own writes, so ``new_len`` may never cut into
    the shared prefix.
    """
    if not 0 <= new_len <= old_len:
        raise ValueError(f"rewind to {new_len} from {old_len}: the new "
                         "horizon must be within the written one")
    if new_len < n_shared * page_size:
        raise ValueError(
            f"rewind to {new_len} tokens would cut into the "
            f"{n_shared}-page radix-shared prefix "
            f"({n_shared * page_size} tokens): shared pages are read-only "
            "— only positions this request wrote itself can rewind")
    if old_len > len(pages) * page_size:
        raise ValueError(f"old_len={old_len} exceeds the "
                         f"{len(pages)}-page holding")
    zero_pairs = [(int(pages[t // page_size]), t % page_size)
                  for t in range(new_len, old_len)]
    first_keep = -(-new_len // page_size)
    n_old = -(-old_len // page_size)
    return zero_pairs, [int(p) for p in pages[first_keep:n_old]]
