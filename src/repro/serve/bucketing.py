"""Bucketed, batched prefill: the ladder, the packing math, and the
attention impl that makes right-padding bit-safe.

Why buckets: the exact-length prefill compiles one program per DISTINCT
prompt length and runs batch-1, so at production arrival rates TTFT is
dominated by compile stalls plus a serial launch per admission. A bucket
ladder right-pads each admitted prompt to the smallest bucket that holds
it and prefills SEVERAL requests in ONE ``[n_req, bucket_len]`` launch —
compile count is bounded by the ladder length and the launch count by the
number of bucket groups, not by arrivals.

Why right-padding is bit-safe here (and only here)
--------------------------------------------------
The bit-identity contract says an engine stream must equal one-shot
``generate()`` bit-for-bit under greedy sampling. A padded forward changes
the KEY-axis extent of every attention reduction, and XLA's dense softmax
re-tiles with it — the low bits of row p's output depend on the TOTAL
padded length, not just on positions [0, p]. The fix is pinned-tile
chunked attention (``model.attention._chunked_core`` with a FIXED kv tile
width, impl string ``"chunked:<kb>"``): the kv axis is reduced tile by
tile in a fori_loop, a fully-masked tile is an exact bitwise no-op
(``corr = exp(m - m) = 1``, ``p = 0``), and a partially-masked tile
reduces over the same ``kb`` lanes whatever the padded total is. Row p's
output then depends ONLY on tiles covering [0, p] — padding on the right
cannot move a single bit, and the batch dimension is bit-transparent by
row independence. The same no-op proof is why the loop is allowed to stop
at each q tile's last live tile: dead tiles (past the causal horizon of
every query in the q tile, such as the pad columns and the junk ctx keys
a cold row of the ctx-aware program carries) are not visited at all, and
no bit moves. ``PREFILL_ATTN_IMPL`` names that impl; every prefill
consumer (bucketed, exact, suffix, one-shot generate) must run it so the
engine's streams and its ``generate()`` reference stay bitwise equal.

Pad positions DO compute junk kv (from pad token 0) which lands in the
tail of the request's last real page — that is safe for the same reason:
masked lanes get score ``-inf`` and exactly-zero weight in f32, decode
overwrites each tail position before it is ever unmasked, and page
donation/preemption only ever moves WHOLE fully-written pages, never a
junk tail (``paged_cache.scatter_prefill_rows`` masks pad ROWS; the
in-page tail is handled by the attention mask).
"""
from __future__ import annotations

from typing import Optional, Tuple

#: The prefill attention impl: flash-style chunked softmax with a PINNED
#: 16-wide kv tile (see module docstring). 16 divides every page size the
#: repo serves and keeps the fori_loop short at smoke scales.
PREFILL_KV_TILE = 16
PREFILL_ATTN_IMPL = f"chunked:{PREFILL_KV_TILE}"


def default_buckets(max_len: int, page_size: int) -> Tuple[int, ...]:
    """The auto ladder: powers-of-two multiples of ``page_size`` with the
    last rung capped at ``max_len`` (every bucket is a whole number of
    pages; the cap keeps the widest program at the engine's horizon).

    >>> default_buckets(48, 8)
    (8, 16, 32, 48)
    >>> default_buckets(32, 8)
    (8, 16, 32)
    """
    out = []
    b = page_size
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


def bucket_for(length: int, buckets: Tuple[int, ...]) -> Optional[int]:
    """Smallest bucket holding ``length``, or None when the ladder tops
    out below it (the caller falls back to the exact-length program)."""
    for b in buckets:
        if length <= b:
            return b
    return None


def rows_for_bucket(bucket: int, cohort_slots: int, budget: int) -> int:
    """Row count of the bucket's compiled program: as many requests as
    the prefill token budget allows at this width, capped by the cohort's
    slot count (more rows than slots can never launch together), floored
    at 1 (a bucket wider than the budget still runs — the scheduler's
    first-admission-ignores-budget rule guarantees such prompts admit).
    A pure function of (bucket, static config), so compile count stays
    <= len(buckets) per cohort."""
    return max(1, min(cohort_slots, budget // bucket))


def validate_buckets(buckets: Tuple[int, ...], *, page_size: int,
                     max_len: int) -> None:
    """Actionable ValueErrors for an explicit ladder (the auto ladder is
    correct by construction)."""
    if tuple(sorted(set(buckets))) != tuple(buckets):
        raise ValueError(
            f"prefill_buckets={buckets} must be strictly increasing: the "
            "packer picks the FIRST bucket that holds the prompt")
    for b in buckets:
        if b <= 0 or b % page_size != 0:
            raise ValueError(
                f"prefill bucket {b} is not a positive multiple of "
                f"page_size={page_size}: the page scatter writes whole "
                "pages, so a partial-page bucket could never land its kv")
        if b > max_len:
            raise ValueError(
                f"prefill bucket {b} exceeds max_len={max_len}: no "
                "admissible prompt can need it (requests longer than "
                "max_len are rejected at submit)")
