"""Decode attention Pallas kernels — one query token against a long KV cache.

Decode (the paper's 1-token generation task) is HBM-bandwidth-bound: the
whole KV cache is read once per token while the MXU does O(L*hd) work. The
kernel streams kv tiles through VMEM with online-softmax statistics in
scratch, emitting the GQA group of q heads that share a kv head together
(one cache read serves g query heads — the GQA arithmetic-intensity win).

Four entry points share two kernel bodies:

  decode_attention            — single layer, contiguous ring cache.
                                Grid (B*Hkv, nL).
  decode_attention_pair       — an LP pair's two layers in ONE launch. The
                                pair caches are stacked contiguously
                                ([2, B, L, Hkv, hd], see
                                repro.model.blocks.group_cache_meta) so the
                                kernel simply grids over (2*B*Hkv, nL): both
                                layers' caches stream through VMEM
                                back-to-back under the same online-softmax
                                machinery, turning the decode attention
                                phase of two LP'd layers into one kernel
                                launch instead of two.
  decode_attention_paged      — single layer against a PAGED cache pool
                                ([n_pages, Hkv, page_size, hd]): instead of
                                a contiguous ring, each grid row streams the
                                pages its request owns, with the block
                                table as a scalar-prefetch operand feeding
                                the k/v BlockSpec index maps (the page id
                                IS the block index — no gather is ever
                                materialised).
  decode_attention_pair_paged — the paged LP pair: one launch for both
                                halves of a stacked pair pool
                                ([2, n_pages, Hkv, page_size, hd]); both
                                halves share ONE block table (an LP pair
                                sits at the same stream position) and the
                                leading pair axis folds into the page index
                                inside the index map.

Grid: (rows, nL|nPages), innermost sequential. The valid horizon ``t`` is a
scalar-prefetch operand (SMEM) so cache positions beyond the current decode
step are masked without recompiling per step; the paged kernels take a
PER-ROW horizon ``t[b]`` (continuous batching: every slot sits at its own
position) and an optional ``head_map`` (third scalar-prefetch operand)
mapping local kv heads to stored pool heads, which is how replicated-kv TP
ranks select their head in-kernel instead of deferring to the XLA gather
path. Pages are head-major (repro.model.attention.seq_to_pages), so one
grid step streams one contiguous ``[page_size, hd]`` tile of one kv head:
a tile the TPU lowering accepts as a block, read exactly once per row.
``interpret`` defaults to auto-detection (compiled on TPU, interpreter
elsewhere — repro.compat).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import resolve_interpret

NEG_INF = -1e30


def _kernel(t_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            bl, nl, scale):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)                 # [g, hd]
    k = k_ref[0].astype(jnp.float32)                 # [bl, hd]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = j * bl + jax.lax.broadcasted_iota(jnp.int32, (bl,), 0)
    s = jnp.where((pos <= t_ref[0])[None, :], s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=-1)
    acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(j == nl - 1)
    def _out():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def _launch(qr, kr, vr, t_valid, *, block_l, interpret):
    """One pallas_call over flattened rows: qr [R, g, hd]; kr, vr [R, L, hd]."""
    R, g, hd = qr.shape
    L = kr.shape[1]
    bl = min(block_l, L)
    pad = (-L) % bl
    if pad:  # padded rows have pos > t_valid -> masked
        kr = jnp.pad(kr, ((0, 0), (0, pad), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, pad), (0, 0)))
    nl = (L + pad) // bl
    t_arr = jnp.asarray(t_valid, jnp.int32).reshape(1)

    kern = functools.partial(_kernel, bl=bl, nl=nl, scale=hd ** -0.5)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((R, g, hd), qr.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, nl),
            in_specs=[pl.BlockSpec((1, g, hd), lambda b, j, t: (b, 0, 0)),
                      pl.BlockSpec((1, bl, hd), lambda b, j, t: (b, j, 0)),
                      pl.BlockSpec((1, bl, hd), lambda b, j, t: (b, j, 0))],
            out_specs=pl.BlockSpec((1, g, hd), lambda b, j, t: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((g,), jnp.float32),
                            pltpu.VMEM((g,), jnp.float32),
                            pltpu.VMEM((g, hd), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(t_arr, qr, kr, vr)


def decode_attention(q, k, v, t_valid, *, block_l=256, interpret=None):
    """q: [B, Hkv, g, hd]; k, v: [B, L, Hkv, hd]; t_valid: scalar int32.
    Returns [B, Hkv, g, hd]."""
    B, Hkv, g, hd = q.shape
    L = k.shape[1]
    qr = q.reshape(B * Hkv, g, hd)
    kr = jnp.moveaxis(k, 2, 1).reshape(B * Hkv, L, hd)
    vr = jnp.moveaxis(v, 2, 1).reshape(B * Hkv, L, hd)
    out = _launch(qr, kr, vr, t_valid, block_l=block_l, interpret=interpret)
    return out.reshape(B, Hkv, g, hd)


def decode_attention_pair(q, k, v, t_valid, *, block_l=256, interpret=None):
    """Fused LP-pair decode attention: ONE launch for both layers.

    q: [2, B, Hkv, g, hd]; k, v: [2, B, L, Hkv, hd] (the stacked pair
    cache); t_valid: scalar int32 shared by both halves (an LP pair is two
    layers at the SAME stream position, so their valid horizons coincide).
    Returns [2, B, Hkv, g, hd].
    """
    P2, B, Hkv, g, hd = q.shape
    assert P2 == 2 and k.shape[0] == 2, (q.shape, k.shape)
    L = k.shape[2]
    qr = q.reshape(2 * B * Hkv, g, hd)
    kr = jnp.moveaxis(k, 3, 2).reshape(2 * B * Hkv, L, hd)
    vr = jnp.moveaxis(v, 3, 2).reshape(2 * B * Hkv, L, hd)
    out = _launch(qr, kr, vr, t_valid, block_l=block_l, interpret=interpret)
    return out.reshape(2, B, Hkv, g, hd)


# ---------------------------------------------------------------------------
# Paged variant: grid over block tables instead of a contiguous ring
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, t_ref, hm_ref, q_ref, k_ref, v_ref, o_ref, m_sc,
                  l_sc, acc_sc, *, ps, n_pg, B, hkv, scale):
    r = pl.program_id(0)
    j = pl.program_id(1)
    b = (r // hkv) % B  # which request's horizon gates this row

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)                 # [g, hd]
    k = k_ref[0, 0].astype(jnp.float32)              # [ps, hd]
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # Logical stream position of page j, slot i is j*ps + i; everything past
    # THIS ROW'S horizon (incl. the whole garbage page 0 reached through
    # unused block-table entries) masks out.
    pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (ps,), 0)
    s = jnp.where((pos <= t_ref[b])[None, :], s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=-1)
    acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(j == n_pg - 1)
    def _out():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def _launch_paged(qr, k_pages, v_pages, block_tables, t_valid, *, n_half,
                  B, hkv, head_map=None, interpret):
    """qr: [R, g, hd] flattened rows (R = nP*B*hkv, pair-major); k/v_pages:
    [nP*n_half, Hkv, ps, hd] with the pair axis folded into the page axis;
    block_tables: [B, n_pg]; t_valid: [B]. The block table is a scalar-
    prefetch operand: the k/v index maps translate (row, page-step) ->
    physical page id, so each row streams exactly the pages its request
    owns — the paged analogue of the ring kernel's sequential L walk.

    ``head_map`` ([hkv] int32, default identity) maps a row's LOCAL kv-head
    index to the STORED head it streams — a third scalar-prefetch operand
    feeding the k/v index maps. This is how a TP rank with REPLICATED kv
    heads (n_kv < tp) selects its kv head(s) inside the kernel: the pool
    keeps all n_kv stored heads and each rank's rows pick theirs, so no
    per-rank kv gather is ever materialised (the selection the XLA path
    does with ``attention.select_local_kv``)."""
    R, g, hd = qr.shape
    ps = k_pages.shape[2]
    n_pg = block_tables.shape[1]
    bt = jnp.asarray(block_tables, jnp.int32)
    t_arr = jnp.asarray(t_valid, jnp.int32).reshape(B)
    if head_map is None:
        head_map = jnp.arange(hkv, dtype=jnp.int32)
    hm = jnp.asarray(head_map, jnp.int32).reshape(hkv)

    def kv_index(r, j, bt_ref, t_ref, hm_ref):
        half = r // (B * hkv)            # 0 (single / first layer) or 1
        b = (r // hkv) % B
        h = r % hkv
        return (half * n_half + bt_ref[b, j], hm_ref[h], 0, 0)

    kern = functools.partial(_paged_kernel, ps=ps, n_pg=n_pg, B=B, hkv=hkv,
                             scale=hd ** -0.5)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((R, g, hd), qr.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, n_pg),
            in_specs=[pl.BlockSpec((1, g, hd),
                                   lambda r, j, bt, t, hm: (r, 0, 0)),
                      pl.BlockSpec((1, 1, ps, hd), kv_index),
                      pl.BlockSpec((1, 1, ps, hd), kv_index)],
            out_specs=pl.BlockSpec((1, g, hd),
                                   lambda r, j, bt, t, hm: (r, 0, 0)),
            scratch_shapes=[pltpu.VMEM((g,), jnp.float32),
                            pltpu.VMEM((g,), jnp.float32),
                            pltpu.VMEM((g, hd), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(bt, t_arr, hm, qr, k_pages, v_pages)


def decode_attention_paged(q, k_pages, v_pages, block_tables, t_valid, *,
                           head_map=None, interpret=None):
    """Paged decode attention, one layer. q: [B, Hkv, g, hd]; k_pages,
    v_pages: [n_pages, Hkv, page_size, hd]; block_tables: [B, n_pg] int32;
    t_valid: [B] int32 per-slot horizons; head_map: optional [Hkv] int32
    mapping q's local kv-head axis to stored pool heads (replicated-kv TP
    ranks — see _launch_paged). Returns [B, Hkv, g, hd]."""
    B, Hkv, g, hd = q.shape
    qr = q.reshape(B * Hkv, g, hd)
    out = _launch_paged(qr, k_pages, v_pages, block_tables, t_valid,
                        n_half=k_pages.shape[0], B=B, hkv=Hkv,
                        head_map=head_map, interpret=interpret)
    return out.reshape(B, Hkv, g, hd)


def decode_attention_pair_paged(q, k_pages, v_pages, block_tables, t_valid,
                                *, head_map=None, interpret=None):
    """Fused paged LP-pair decode: ONE launch for both halves.

    q: [2, B, Hkv, g, hd]; k_pages, v_pages: [2, n_pages, Hkv, page_size,
    hd] (the stacked pair pool); block_tables: [B, n_pg] SHARED by both
    halves (an LP pair sits at the same stream position, so its two layers
    occupy the same page indices of their own half); t_valid: [B] int32;
    head_map: optional [Hkv] int32 local-head -> stored-head selection,
    shared by both halves. Returns [2, B, Hkv, g, hd].
    """
    P2, B, Hkv, g, hd = q.shape
    assert P2 == 2 and k_pages.shape[0] == 2, (q.shape, k_pages.shape)
    n_half = k_pages.shape[1]
    qr = q.reshape(2 * B * Hkv, g, hd)
    kf = k_pages.reshape(2 * n_half, *k_pages.shape[2:])
    vf = v_pages.reshape(2 * n_half, *v_pages.shape[2:])
    out = _launch_paged(qr, kf, vf, block_tables, t_valid, n_half=n_half,
                        B=B, hkv=Hkv, head_map=head_map, interpret=interpret)
    return out.reshape(2, B, Hkv, g, hd)
