"""Tensor-parallel multi-head attention for every assigned mixer kind.

TP conventions (per-rank shapes inside shard_map):
  * Q heads are padded to a multiple of tp (zero wo rows => padded heads are
    inert) and sharded over the ``model`` axis: hq = hq_global // tp.
  * KV heads are sharded when n_kv >= tp (minicpm, whisper) and replicated
    otherwise. In every replicated case of the assigned pool each rank's q
    heads map to exactly ONE kv head (group % hq == 0), so the rank selects
    its kv head dynamically and runs a grouped (g = hq) attention core —
    no KV expansion is ever materialised.

LP pairs reuse this module with a leading pair axis on the weights: one
einsum projects both layers' Q/K/V ("the stacked matmul" of the paper's
Fig. 5), the head axis simply doubles, and the pair's output projection is a
single contraction that also sums the two paths — the psum that follows is
the paper's ONE sync point for the attention phase of two layers.

On the decode path the pair's KV caches are stacked-contiguous
([2, B, L, Hkv, hd] — repro.model.blocks.group_cache_meta), and
``decode_attn_standard`` / ``decode_attn_seq_sharded`` with ``pair=True``
run both layers as one wide unit: one stacked projection, one cache write,
one attention core (or one ``decode_attention_pair`` Pallas launch when
``set_decode_impl("pallas")``), one merged output projection.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.model.params import PD
from repro.model.rope import apply_rope
from repro.parallel.context import ParallelContext

NEG_INF = -1e30

_DECODE_IMPL = "xla"


def set_decode_impl(impl: str) -> None:
    """'xla' (default) or 'pallas' (repro.kernels.decode_attention)."""
    global _DECODE_IMPL
    assert impl in ("xla", "pallas"), impl
    _DECODE_IMPL = impl


def get_decode_impl() -> str:
    """Current decode implementation (for save/restore around benchmarks)."""
    return _DECODE_IMPL


# ---------------------------------------------------------------------------
# Static dimension bookkeeping
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AttnDims:
    tp: int
    hq_global: int      # padded global q heads
    hq: int             # local q heads
    kv_sharded: bool
    hkv_global: int     # stored global kv heads (padded when sharded)
    hkv: int            # local kv heads held by each rank
    group: int          # ORIGINAL q-heads per kv-head (GQA group)
    hd: int
    per_head: bool = False  # rank q-heads span kv groups -> per-head kv gather


def attn_dims(cfg, tp: int) -> AttnDims:
    if cfg.n_heads == 0:  # attention-free arch (falcon-mamba)
        return AttnDims(tp, 0, 0, False, 0, 0, 1, cfg.head_dim or 1)
    hq_global = -(-cfg.n_heads // tp) * tp
    hq = hq_global // tp
    kv_sharded = cfg.n_kv_heads >= tp
    per_head = False
    if kv_sharded:
        hkv_global = -(-cfg.n_kv_heads // tp) * tp
        hkv = hkv_global // tp
        assert hq % hkv == 0, (hq, hkv)
    else:
        hkv_global = cfg.n_kv_heads
        hkv = cfg.n_kv_heads
    group = max(cfg.n_heads // max(cfg.n_kv_heads, 1), 1)
    if not kv_sharded and tp > 1 and group % hq != 0:
        # Rank q-heads span GQA groups (llama4: 40 q heads padded to 48 over
        # 16 ranks, group=5, hq=3). Each rank gathers ITS q-heads' kv heads
        # (hq x hd per rank — negligible) and runs a g=1 grouped core.
        per_head = True
    return AttnDims(tp, hq_global, hq, kv_sharded, hkv_global, hkv, group,
                    cfg.head_dim, per_head)


def attn_template(cfg, tp: int, *, cross: bool = False):
    d = attn_dims(cfg, tp)
    D = cfg.d_model
    kv_spec = P(None, "model") if d.kv_sharded else P()
    t = {
        "wq": PD((D, d.hq_global * d.hd), P(None, "model")),
        "wk": PD((D, d.hkv_global * d.hd), kv_spec),
        "wv": PD((D, d.hkv_global * d.hd), kv_spec),
        "wo": PD((d.hq_global * d.hd, D), P("model", None)),
    }
    if getattr(cfg, "attn_bias", False):
        kv_bspec = P("model") if d.kv_sharded else P()
        t["bq"] = PD((d.hq_global * d.hd,), P("model"), init="zeros")
        t["bk"] = PD((d.hkv_global * d.hd,), kv_bspec, init="zeros")
        t["bv"] = PD((d.hkv_global * d.hd,), kv_bspec, init="zeros")
        t["bo"] = PD((D,), P(), init="zeros")
    return t


# ---------------------------------------------------------------------------
# Masking
# ---------------------------------------------------------------------------

def tile_mask(kind: str, qpos, kpos, *, window=0, chunk=0, prefix_len=0):
    """Boolean allowed-mask for absolute q positions x k positions.

    ``qpos`` may be [qb] (one offset for the whole batch) or [B, qb]
    (per-row offsets — the bucketed radix-suffix path, where every row
    starts at its own ctx length); the mask is [qb, kb] or [B, qb, kb]
    respectively."""
    q = qpos[..., None]
    k = kpos[None, :]
    if kind == "attn_bidir":
        return jnp.ones(qpos.shape + kpos.shape, bool)
    causal = k <= q
    if kind in ("attn", "attn_global"):
        if prefix_len:
            return causal | (k < prefix_len)
        return causal
    if kind == "attn_local":
        return causal & (q - k < window)
    if kind == "attn_chunked":
        return causal & (q // chunk == k // chunk)
    raise ValueError(kind)


def _uses_rope(cfg, kind: str) -> bool:
    return cfg.pos_embed == "rope" and kind not in ("attn_global", "attn_bidir")


# ---------------------------------------------------------------------------
# Attention cores (grouped layout: q [B,S,Hk,g,hd], kv [B,T,Hk,hd])
# ---------------------------------------------------------------------------

def _dense_core(q, k, v, mask):
    """Materialised-scores reference core (small S*T only)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bsngh,btnh->bngst", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale + jnp.where(mask, 0.0, NEG_INF)[:, None, None, :, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngst,btnh->bsngh", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def _chunked_core(q, k, v, *, kind, window, chunk, prefix_len, q0, k0,
                  qb: int, kb: int, fixed_kb: bool = False):
    """Online-softmax (flash-style) core: O(S*block) memory, scan over q and
    kv tiles. ``q0``/``k0`` are the absolute offsets of q and k position 0.
    This is the XLA path; the Pallas kernel implements the same schedule on
    TPU (repro.kernels.flash_attention). Ragged S/T are padded to tile
    multiples; padded kv columns are masked via ``k_limit``.

    ``fixed_kb`` pins the kv tile width at ``kb`` even when T < kb (pad up
    instead of clamping down). With a pinned tile, the reduction grouping
    of every q row is a pure function of its own key horizon: a fully
    masked tile contributes ``corr = exp(m - m) = 1`` and ``p = 0``, so
    ``l = l * 1 + 0`` and ``acc = acc * 1 + 0`` are bitwise no-ops, and a
    partially masked tile reduces over the same ``kb`` lanes whatever the
    total padded length is. That makes right-padding the key axis BIT-
    TRANSPARENT for rows below the true length — the property the serve
    engine's bucketed prefill leans on (masked pad lanes carry finite
    values, so ``0 * v`` is exactly 0).

    ``q0`` may be an [B]-shaped array of PER-ROW offsets (the bucketed
    radix-suffix path: row i's queries start at its own ctx length); the
    mask then resolves per row while the tile schedule — and with
    ``fixed_kb`` the reduction grouping — stays row-independent."""
    B, S0, Hk, g, hd = q.shape
    T0 = k.shape[1]
    qb = min(qb, S0)
    if not fixed_kb:
        kb = min(kb, T0)
    pad_q = (-S0) % qb
    pad_k = (-T0) % kb
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    S, T = S0 + pad_q, T0 + pad_k
    k_limit = k0 + T0
    scale = hd ** -0.5
    nq, nk = S // qb, T // kb

    qt = q.reshape(B, nq, qb, Hk, g, hd).transpose(1, 0, 3, 4, 2, 5)  # [nq,B,Hk,g,qb,hd]
    kt = k.reshape(B, nk, kb, Hk, hd).transpose(1, 0, 3, 2, 4)        # [nk,B,Hk,kb,hd]
    vt = v.reshape(B, nk, kb, Hk, hd).transpose(1, 0, 3, 2, 4)

    per_row = getattr(q0, "ndim", 0) > 0   # q0 is [B]: per-row offsets

    def q_step(_, qi_and_tile):
        qi, qtile = qi_and_tile
        base = q0[:, None] if per_row else q0
        qpos = base + qi * qb + jnp.arange(qb)   # [qb] or [B,qb]

        def kv_step(carry, ki_and_tiles):
            m, l, acc = carry
            ki, ktile, vtile = ki_and_tiles
            kpos = k0 + ki * kb + jnp.arange(kb)
            msk = tile_mask(kind, qpos, kpos, window=window, chunk=chunk,
                            prefix_len=prefix_len)  # [qb,kb] or [B,qb,kb]
            msk = msk & (kpos < k_limit)[None, :]   # kv padding columns
            s = jnp.einsum("bngqh,bnkh->bngqk", qtile.astype(jnp.float32),
                           ktile.astype(jnp.float32)) * scale
            bias = jnp.where(msk, 0.0, NEG_INF)
            s = s + (bias[:, None, None, :, :] if per_row
                     else bias[None, None, None, :, :])
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bngqk,bnkh->bngqh", p, vtile.astype(jnp.float32))
            acc = acc * corr[..., None] + pv
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hk, g, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hk, g, qb), jnp.float32)
        a0 = jnp.zeros((B, Hk, g, qb, hd), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), (jnp.arange(nk), kt, vt))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, tiles = lax.scan(q_step, None, (jnp.arange(nq), qt))  # [nq,B,Hk,g,qb,hd]
    out = tiles.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, Hk, g, hd)
    return out[:, :S0] if pad_q else out


_PALLAS_KIND = {"attn": "causal", "attn_global": "causal",
                "attn_local": "window", "attn_chunked": "chunk",
                "attn_bidir": "bidir"}


def attention_core(q, k, v, *, kind, window=0, chunk=0, prefix_len=0,
                   q0=0, k0=0, impl="auto", qb=512, kb=1024):
    B, S, Hk, g, hd = q.shape
    T = k.shape[1]
    if getattr(q0, "ndim", 0) > 0 and not impl.startswith("chunked:"):
        # Per-row offsets are only wired through the pinned-tile chunked
        # core (the serve prefill impl); the dense/pallas paths would
        # silently build a single shared mask from the wrong-rank qpos.
        raise NotImplementedError(
            f"per-row q0 requires a pinned chunked impl ('chunked:<kb>'), "
            f"got impl={impl!r}")
    if impl.startswith("chunked:"):
        # Pinned kv tile width ("chunked:16" -> kb=16, never clamped to T):
        # the serve prefill path uses this so bucket-padded and exact-length
        # forwards reduce with identical per-tile grouping (see
        # _chunked_core fixed_kb).
        kb = int(impl.split(":", 1)[1])
        return _chunked_core(q, k, v, kind=kind, window=window, chunk=chunk,
                             prefix_len=prefix_len, q0=q0, k0=k0, qb=qb,
                             kb=kb, fixed_kb=True)
    if impl == "auto":
        impl = "dense" if S * T <= 2048 * 2048 else "chunked"
    if impl == "pallas":
        # GQA-folded flash kernel: rows of one kv head = [position, group].
        from repro.kernels import ops as KOPS
        qf = q.transpose(0, 2, 1, 3, 4).reshape(B * Hk, S * g, hd)
        kf = jnp.moveaxis(k, 2, 1).reshape(B * Hk, T, hd)
        vf = jnp.moveaxis(v, 2, 1).reshape(B * Hk, T, hd)
        o = KOPS.flash_attention(qf, kf, vf, kind=_PALLAS_KIND[kind],
                                 window=window, chunk=chunk,
                                 prefix_len=prefix_len, q0=q0, k0=k0,
                                 q_group=g)
        return o.reshape(B, Hk, S, g, hd).transpose(0, 2, 1, 3, 4)
    if impl == "dense":
        qpos = q0 + jnp.arange(S)
        kpos = k0 + jnp.arange(T)
        mask = tile_mask(kind, qpos, kpos, window=window, chunk=chunk,
                         prefix_len=prefix_len)[None]
        mask = jnp.broadcast_to(mask, (B, S, T))
        return _dense_core(q, k, v, mask)
    return _chunked_core(q, k, v, kind=kind, window=window, chunk=chunk,
                         prefix_len=prefix_len, q0=q0, k0=k0, qb=qb, kb=kb)


# ---------------------------------------------------------------------------
# Projections (single layer and LP pair) + rank-local KV selection
# ---------------------------------------------------------------------------

def _proj(x, w, b, tp):
    y = x @ w.astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def _proj_pair(xs, w, b):
    """xs: [2,B,S,D] (per-path normalised inputs); w: [2,D,C] -> [2,B,S,C].
    One batched matmul for both paths == the paper's stacked projection."""
    y = jnp.einsum("pbsd,pdc->pbsc", xs, w.astype(xs.dtype))
    if b is not None:
        y = y + b[:, None, None, :].astype(y.dtype)
    return y


def rank_head_kv_map(dims: AttnDims, pc: ParallelContext):
    """[hq] kv-head index for each of this rank's q heads (per-head mode).
    Padded q heads clip to the last kv head (their wo rows are zero)."""
    base = pc.tp_index() * dims.hq
    return jnp.clip((base + jnp.arange(dims.hq)) // dims.group,
                    0, dims.hkv - 1)


def select_local_kv(kv, dims: AttnDims, pc: ParallelContext, *,
                    head_axis: int = 2):
    """kv as stored ([B,T,hkv,hd], head axis 2). Returns the rank-local
    selection for the grouped core: hkv heads when sharded; 1 (this rank's
    kv head) when replicated and the rank's q block lives in one GQA group;
    hq per-head gathered otherwise."""
    if dims.kv_sharded or dims.tp == 1:
        return kv
    if dims.per_head:
        return jnp.take(kv, rank_head_kv_map(dims, pc), axis=head_axis)
    base = pc.tp_index() * dims.hq
    kv_idx = jnp.clip(base // dims.group, 0, dims.hkv - 1)
    return lax.dynamic_slice_in_dim(kv, kv_idx, 1, axis=head_axis)


def select_local_kv_pair(kv, dims: AttnDims, pc: ParallelContext):
    """Stacked-pair variant: kv [2,B,T,hkv,hd] -> [2,B,T,Hk_eff,hd]. The
    same selection on head axis 3 so the pair stays one contiguous tensor
    for the fused decode kernel."""
    return select_local_kv(kv, dims, pc, head_axis=3)


def paged_head_map(dims: AttnDims, pc: ParallelContext):
    """Local-head -> STORED-head map for the paged decode kernels, or None
    when the identity applies (tp == 1, or kv heads sharded so each rank's
    pool shard already holds exactly its heads).

    This is ``select_local_kv`` expressed as an index map instead of a
    gather: the paged pool keeps all stored kv heads replicated across
    ranks, and the kernel's BlockSpec index map streams only the head(s)
    this rank's q rows need (repro.kernels.decode_attention._launch_paged),
    so replicated-kv TP never materialises a per-rank kv selection on the
    Pallas path.
    """
    if dims.tp == 1 or dims.kv_sharded:
        return None
    if dims.per_head:
        return rank_head_kv_map(dims, pc)            # [hq], g = 1
    base = pc.tp_index() * dims.hq
    kv_idx = jnp.clip(base // dims.group, 0, dims.hkv - 1)
    return kv_idx[None]                              # [1], g = hq


def core_layout(dims: AttnDims) -> Tuple[int, int]:
    """(Hk_eff, g) of the grouped core for one layer's local heads."""
    if dims.tp == 1 or dims.kv_sharded:
        assert dims.hq % dims.hkv == 0, (dims.hq, dims.hkv)
        return dims.hkv, dims.hq // dims.hkv
    if dims.per_head:
        return dims.hq, 1  # per-head gathered kv
    return 1, dims.hq  # replicated kv: one rank = one kv head, g = hq


def project_q(p, xn, cfg, dims: AttnDims, *, positions, kind, pair: bool):
    """q in folded layout [B,S,P*hq,hd] (pair-interleaved by... pair-MAJOR? No:
    pair axis folds as [2, hq] per position -> heads [2*hq], layer-a first)."""
    bq = p.get("bq")
    if pair:
        B, S = xn.shape[1], xn.shape[2]
        q = _proj_pair(xn, p["wq"], bq)
        q = q.transpose(1, 2, 0, 3).reshape(B, S, 2 * dims.hq, dims.hd)
    else:
        B, S = xn.shape[0], xn.shape[1]
        q = _proj(xn, p["wq"], bq, dims.tp).reshape(B, S, dims.hq, dims.hd)
    if _uses_rope(cfg, kind):
        q = apply_rope(q, positions, cfg.rope_theta)
    return q


def project_kv(p, xn, cfg, dims: AttnDims, *, positions, kind, pair: bool):
    """k, v in stored layout [B,S,P*hkv,hd] (pair folded into the head axis).
    ``xn`` is the self-attention input, or the (raw) encoder output for
    cross-attention (kind='attn_bidir' -> no rope on keys)."""
    bk = p.get("bk"); bv = p.get("bv")
    if pair:
        B, S = xn.shape[1], xn.shape[2]
        k = _proj_pair(xn, p["wk"], bk).transpose(1, 2, 0, 3).reshape(B, S, 2 * dims.hkv, dims.hd)
        v = _proj_pair(xn, p["wv"], bv).transpose(1, 2, 0, 3).reshape(B, S, 2 * dims.hkv, dims.hd)
    else:
        B, S = xn.shape[0], xn.shape[1]
        k = _proj(xn, p["wk"], bk, dims.tp).reshape(B, S, dims.hkv, dims.hd)
        v = _proj(xn, p["wv"], bv, dims.tp).reshape(B, S, dims.hkv, dims.hd)
    if _uses_rope(cfg, kind):
        k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


def project_qkv(p, xn, cfg, dims: AttnDims, pc, *, positions, kind, pair: bool):
    """Self-attention q/k/v from the same normalised input."""
    q = project_q(p, xn, cfg, dims, positions=positions, kind=kind, pair=pair)
    k, v = project_kv(p, xn, cfg, dims, positions=positions, kind=kind, pair=pair)
    return q, k, v


# ---------------------------------------------------------------------------
# Decode (single new token against a KV cache)
# ---------------------------------------------------------------------------

def cache_slot(kind: str, t, *, window=0, chunk=0):
    """Ring-buffer slot + local validity horizon for a decode step ``t``.

    Returns (slot_index, t_local) where entries with arange(L) <= t_local are
    valid. For plain causal caches slot == t; window/chunked kinds reuse a
    ring of size window/chunk.
    """
    if kind == "attn_local" and window:
        return t % window, jnp.minimum(t, window - 1)
    if kind == "attn_chunked" and chunk:
        return t % chunk, t % chunk
    return t, t


def decode_attn_standard(p, xn, cache_k, cache_v, t, cfg, dims: AttnDims, pc,
                         *, kind, pair: bool, window=0, chunk=0):
    """Decode with head-local caches. Returns (partial_out, new_k, new_v).

    pair=False: xn [B,1,D], cache_[kv] [B, L, hkv_stored, hd].
    pair=True (fused LP pair): xn [2,B,1,D] (both per-path norms of the same
    residual), cache_[kv] [2, B, L, hkv_stored, hd] STACKED-CONTIGUOUS.
    Both layers run as one wide unit: ONE stacked QKV projection einsum,
    ONE ring-slot write per cache tensor, ONE attention core / kernel
    launch over the leading pair axis, ONE merged output projection — the
    caller's psum after this is the pair's single attention-phase sync.

    hkv_stored == n_kv (replicated) or hkv (sharded).
    """
    B = xn.shape[1] if pair else xn.shape[0]
    pos = jnp.asarray(t)[None] if jnp.ndim(t) == 0 else t
    q, k, v = project_qkv(p, xn, cfg, dims, pc, positions=pos, kind=kind, pair=pair)
    slot, t_local = cache_slot(kind, t, window=window, chunk=chunk)
    Hk, g = core_layout(dims)
    scale = dims.hd ** -0.5

    if pair:
        hkv_st = cache_k.shape[3]
        L = cache_k.shape[2]
        # New-token kv arrives pair-folded [B,1,2*hkv,hd]; unfold to the
        # stacked layout and write BOTH layers' slots in one update.
        k2 = k.reshape(B, 1, 2, hkv_st, dims.hd).transpose(2, 0, 1, 3, 4)
        v2 = v.reshape(B, 1, 2, hkv_st, dims.hd).transpose(2, 0, 1, 3, 4)
        cache_k = lax.dynamic_update_slice_in_dim(
            cache_k, k2.astype(cache_k.dtype), slot, axis=2)
        cache_v = lax.dynamic_update_slice_in_dim(
            cache_v, v2.astype(cache_v.dtype), slot, axis=2)
        ks = select_local_kv_pair(cache_k, dims, pc)   # [2,B,L,Hk,hd]
        vs = select_local_kv_pair(cache_v, dims, pc)
        qh = q.reshape(B, 2, Hk, g, dims.hd)           # pair-major heads, S=1
        if _DECODE_IMPL == "pallas":
            from repro.kernels import ops as KOPS
            qp = qh.transpose(1, 0, 2, 3, 4)           # [2,B,Hk,g,hd]
            o = KOPS.decode_attention_pair(qp, ks, vs, t_local).astype(xn.dtype)
            o = o.transpose(1, 0, 2, 3, 4).reshape(B, 1, 2 * dims.hq, dims.hd)
            return output_proj(p, o, dims, pair=True), cache_k, cache_v
        s = jnp.einsum("bpngh,pbtnh->bpngt", qh.astype(jnp.float32),
                       ks.astype(jnp.float32)) * scale
        valid = (jnp.arange(L) <= t_local)[None, None, None, None, :]
        s = jnp.where(valid, s, NEG_INF)
        pweights = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bpngt,pbtnh->bpngh", pweights, vs.astype(jnp.float32))
        o = o.astype(xn.dtype).reshape(B, 1, 2 * dims.hq, dims.hd)
        return output_proj(p, o, dims, pair=True), cache_k, cache_v

    cache_k = lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), slot, axis=1)
    cache_v = lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), slot, axis=1)
    L = cache_k.shape[1]
    ks = select_local_kv(cache_k, dims, pc)
    vs = select_local_kv(cache_v, dims, pc)
    qh = q.reshape(B, 1, Hk, g, dims.hd)
    if _DECODE_IMPL == "pallas":
        from repro.kernels import ops as KOPS
        o = KOPS.decode_attention(qh[:, 0], ks, vs, t_local).astype(xn.dtype)
        o = o.reshape(B, 1, dims.hq, dims.hd)
        return output_proj(p, o, dims, pair=False), cache_k, cache_v
    s = jnp.einsum("bsngh,btnh->bngst", qh.astype(jnp.float32), ks.astype(jnp.float32)) * scale
    valid = (jnp.arange(L) <= t_local)[None, None, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    pweights = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngst,btnh->bsngh", pweights, vs.astype(jnp.float32))
    o = o.astype(xn.dtype).reshape(B, 1, dims.hq, dims.hd)
    return output_proj(p, o, dims, pair=False), cache_k, cache_v


def seq_to_pages(x, axis: int, page_size: int):
    """Stored kv ``[..., L, H, hd]`` (L at ``axis``) -> the paged pool's
    page layout ``[..., L / page_size, H, page_size, hd]``.

    Pages are head-major inside: one (page, kv head) block is a contiguous
    ``[page_size, hd]`` tile, which is what the paged decode kernels'
    BlockSpecs stream (a TPU block's last two dims must be tile-aligned or
    whole, so a ``[page_size, 1, hd]`` slice of a token-major page cannot
    be a block). Every producer and consumer of pool pages converts through
    this pair of functions.
    """
    s = x.shape
    x = x.reshape(*s[:axis], s[axis] // page_size, page_size, *s[axis + 1:])
    return jnp.swapaxes(x, axis + 1, axis + 2)


def pages_to_seq(x, axis: int):
    """Inverse of ``seq_to_pages``: pool pages ``[..., n_pg, H, ps, hd]``
    (n_pg at ``axis``) -> stored kv ``[..., n_pg * ps, H, hd]``."""
    x = jnp.swapaxes(x, axis + 1, axis + 2)
    s = x.shape
    return x.reshape(*s[:axis], s[axis] * s[axis + 1], *s[axis + 2:])


def decode_attn_paged(p, xn, k_pages, v_pages, t, block_tables, cfg,
                      dims: AttnDims, pc, *, kind, pair: bool):
    """Decode against the PAGED cache pool (continuous batching).

    pair=False: xn [B,1,D], k/v_pages [n_pages, hkv_stored, ps, hd].
    pair=True (fused LP pair): xn [2,B,1,D], k/v_pages [2, n_pages,
    hkv_stored, ps, hd] stacked-contiguous — both halves occupy the SAME
    page indices of their own half, so one block table serves the pair and
    the pair still costs ONE projection, ONE scatter per cache tensor, ONE
    attention launch and ONE merged output projection.

    t: [B] int32 per-slot absolute positions (every slot decodes at its own
    stream position); block_tables: [B, n_pg] int32 page indirection, with
    unused entries (and idle slots' whole rows) pointing at the reserved
    garbage page 0 — their writes are harmless and their reads mask out.
    Only plain causal kinds page (slot == t); window/chunk rings are
    rejected upstream (serve.paged_cache.validate_paged_support).

    TP: kv-sharded pools hold this rank's heads (identity head map);
    replicated-kv ranks select their kv head(s) in-kernel through
    ``paged_head_map`` on the Pallas path and ``select_local_kv`` on the
    XLA gather path — both run under tp > 1.

    Returns (partial_out, new_k_pages, new_v_pages).
    """
    B = xn.shape[1] if pair else xn.shape[0]
    q, k, v = project_qkv(p, xn, cfg, dims, pc, positions=t[:, None],
                          kind=kind, pair=pair)
    page_ax = 1 if pair else 0
    hkv_st = k_pages.shape[page_ax + 1]
    ps = k_pages.shape[page_ax + 2]
    # Indirection: position t lives at (bt[b, t // ps], t % ps).
    page_of = jnp.take_along_axis(block_tables, (t // ps)[:, None],
                                  axis=1)[:, 0]
    off = t % ps
    Hk, g = core_layout(dims)
    scale = dims.hd ** -0.5

    if pair:
        # New-token kv arrives pair-folded [B,1,2*hkv,hd]. Indexing the
        # pool at [:, page_of, :, off] yields [B, 2, hkv, hd] (the advanced
        # axes lead), so both halves' slots land in ONE scatter per tensor.
        k2 = k.reshape(B, 2, hkv_st, dims.hd).astype(k_pages.dtype)
        v2 = v.reshape(B, 2, hkv_st, dims.hd).astype(v_pages.dtype)
        k_pages = k_pages.at[:, page_of, :, off].set(k2)
        v_pages = v_pages.at[:, page_of, :, off].set(v2)
        qh = q.reshape(B, 2, Hk, g, dims.hd)           # pair-major heads, S=1
        if _DECODE_IMPL == "pallas":
            from repro.kernels import ops as KOPS
            qp = qh.transpose(1, 0, 2, 3, 4)           # [2,B,Hk,g,hd]
            o = KOPS.decode_attention_pair_paged(
                qp, k_pages, v_pages, block_tables, t,
                paged_head_map(dims, pc)).astype(xn.dtype)
            o = o.transpose(1, 0, 2, 3, 4).reshape(B, 1, 2 * dims.hq, dims.hd)
            return output_proj(p, o, dims, pair=True), k_pages, v_pages
        # XLA path: gather the slots' pages back into per-request sequences
        # ([2, B, L, hkv, hd], L = n_pg * ps) and run the ring core math.
        kg = pages_to_seq(jnp.take(k_pages, block_tables, axis=1), 2)
        vg = pages_to_seq(jnp.take(v_pages, block_tables, axis=1), 2)
        L = kg.shape[2]
        ks = select_local_kv_pair(kg, dims, pc)
        vs = select_local_kv_pair(vg, dims, pc)
        s = jnp.einsum("bpngh,pbtnh->bpngt", qh.astype(jnp.float32),
                       ks.astype(jnp.float32)) * scale
        valid = jnp.arange(L)[None, :] <= t[:, None]   # per-slot horizon
        s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
        pweights = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bpngt,pbtnh->bpngh", pweights, vs.astype(jnp.float32))
        o = o.astype(xn.dtype).reshape(B, 1, 2 * dims.hq, dims.hd)
        return output_proj(p, o, dims, pair=True), k_pages, v_pages

    k_pages = k_pages.at[page_of, :, off].set(k[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[page_of, :, off].set(v[:, 0].astype(v_pages.dtype))
    qh = q.reshape(B, 1, Hk, g, dims.hd)
    if _DECODE_IMPL == "pallas":
        from repro.kernels import ops as KOPS
        o = KOPS.decode_attention_paged(
            qh[:, 0], k_pages, v_pages, block_tables, t,
            paged_head_map(dims, pc)).astype(xn.dtype)
        o = o.reshape(B, 1, dims.hq, dims.hd)
        return output_proj(p, o, dims, pair=False), k_pages, v_pages
    kg = pages_to_seq(jnp.take(k_pages, block_tables, axis=0), 1)
    vg = pages_to_seq(jnp.take(v_pages, block_tables, axis=0), 1)
    L = kg.shape[1]
    ks = select_local_kv(kg, dims, pc)
    vs = select_local_kv(vg, dims, pc)
    s = jnp.einsum("bsngh,btnh->bngst", qh.astype(jnp.float32),
                   ks.astype(jnp.float32)) * scale
    valid = jnp.arange(L)[None, :] <= t[:, None]
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    pweights = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngst,btnh->bsngh", pweights, vs.astype(jnp.float32))
    o = o.astype(xn.dtype).reshape(B, 1, dims.hq, dims.hd)
    return output_proj(p, o, dims, pair=False), k_pages, v_pages


def decode_attn_seq_sharded(p, xn, cache_k, cache_v, t, cfg, dims: AttnDims, pc,
                            *, kind, pair: bool, window=0, chunk=0):
    """Decode with the KV cache sharded along SEQUENCE over the model axis
    (for kv_heads < tp: avoids tp-fold cache replication, multiplies the
    aggregate HBM bandwidth of the cache read by tp).

    cache_[kv]: [B, L/tp, n_kv, hd] per rank; pair=True uses the stacked
    layout [2, B, L/tp, n_kv, hd] and runs BOTH layers through one gathered
    attention evaluation. Combines partial softmax stats across ranks with
    one pmax + ONE packed psum per phase regardless of pair width.
    """
    nP = 2 if pair else 1
    B = xn.shape[1] if pair else xn.shape[0]
    pos = jnp.asarray(t)[None] if jnp.ndim(t) == 0 else t
    q, k, v = project_qkv(p, xn, cfg, dims, pc, positions=pos, kind=kind, pair=pair)
    # q: [B,1,nP*hq,hd] local -> gather all q heads.
    qg = pc.all_gather_tp(q, axis=2)  # [B,1,tp*nP*hq,hd] rank-major
    tp = dims.tp
    if pair:
        qg = qg.reshape(B, 1, tp, 2, dims.hq, dims.hd).transpose(0, 1, 3, 2, 4, 5)
        qg = qg.reshape(B, 1, 2, tp * dims.hq, dims.hd)
    else:
        qg = qg.reshape(B, 1, 1, tp * dims.hq, dims.hd)

    # Cache update: only the owner rank of slot ``t`` writes.
    slot, t_local = cache_slot(kind, t, window=window, chunk=chunk)
    seq_ax = 2 if pair else 1
    L_loc = cache_k.shape[seq_ax]
    n_kv = cache_k.shape[seq_ax + 1]
    rank = pc.tp_index()
    local_slot = slot - rank * L_loc
    in_range = (local_slot >= 0) & (local_slot < L_loc)
    idx = jnp.clip(local_slot, 0, L_loc - 1)
    if pair:  # unfold the pair-folded new token to the stacked layout
        kn = k.reshape(B, 1, 2, n_kv, dims.hd).transpose(2, 0, 1, 3, 4)
        vn = v.reshape(B, 1, 2, n_kv, dims.hd).transpose(2, 0, 1, 3, 4)
    else:
        kn, vn = k, v
    old_k = lax.dynamic_slice_in_dim(cache_k, idx, 1, axis=seq_ax)
    old_v = lax.dynamic_slice_in_dim(cache_v, idx, 1, axis=seq_ax)
    new_k = jnp.where(in_range, kn.astype(cache_k.dtype), old_k)
    new_v = jnp.where(in_range, vn.astype(cache_v.dtype), old_v)
    cache_k = lax.dynamic_update_slice_in_dim(cache_k, new_k, idx, axis=seq_ax)
    cache_v = lax.dynamic_update_slice_in_dim(cache_v, new_v, idx, axis=seq_ax)

    Hq_all = tp * dims.hq          # == padded global q heads
    ks = cache_k if pair else cache_k[None]   # [nP,B,L_loc,n_kv,hd]
    vs = cache_v if pair else cache_v[None]
    if dims.per_head or Hq_all != dims.group * n_kv:
        # Expand kv per q head with the TRUE head->kv map (padded q heads
        # clip; their wo rows are zero). The uniform grouped reshape below
        # is only valid when padding did not inflate the global head count
        # (Hq_all == group * n_kv); otherwise head i's kv is i // group
        # clipped, not i // (Hq_all // n_kv).
        hmap = jnp.clip(jnp.arange(Hq_all) // dims.group, 0, n_kv - 1)
        ks = jnp.take(ks, hmap, axis=3)
        vs = jnp.take(vs, hmap, axis=3)
        n_kv_eff, g = Hq_all, 1
    else:
        n_kv_eff, g = n_kv, Hq_all // max(n_kv, 1)
    qh = qg.reshape(B, 1, nP, n_kv_eff, g, dims.hd)

    scale = dims.hd ** -0.5
    s = jnp.einsum("bspngh,pbtnh->bpngst", qh.astype(jnp.float32), ks.astype(jnp.float32)) * scale
    s = s[..., 0, :]  # squeeze q-position -> [B,P,n,g,L_loc]
    gpos = rank * L_loc + jnp.arange(L_loc)
    valid = gpos <= t_local
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    m_g = pc.pmax_tp(m)
    pexp = jnp.exp(s - m_g[..., None])
    l = pexp.sum(axis=-1)
    acc = jnp.einsum("bpngt,pbtnh->bpngh", pexp, vs.astype(jnp.float32))
    # ONE stacked psum for (l, acc).
    packed = jnp.concatenate([acc, l[..., None]], axis=-1)
    packed = pc.psum_tp(packed)
    acc, l = packed[..., :-1], packed[..., -1]
    o_all = acc / jnp.maximum(l, 1e-30)[..., None]  # [B,P,n_eff,g,hd]
    o_all = o_all.reshape(B, nP, Hq_all, dims.hd)
    # Slice back this rank's q heads.
    o_loc = lax.dynamic_slice_in_dim(o_all, rank * dims.hq, dims.hq, axis=2)
    o = o_loc.reshape(B, nP * dims.hq, dims.hd)[:, None]  # pair-major [B,1,nP*hq,hd]
    return output_proj(p, o, dims, pair=pair), cache_k, cache_v


def output_proj(p, o, dims: AttnDims, *, pair: bool):
    """o: [B,S,P*hq,hd] -> partial [B,S,D] (caller runs phase_out)."""
    B, S = o.shape[0], o.shape[1]
    if pair:
        # Pair output projection as two per-path gemms + one explicit add.
        # The einsum form ("pbsc,pcd->bsd") contracts (p, c) jointly and
        # XLA's split of that reduction can depend on the sequence length,
        # which breaks the suffix-prefill bit-identity contract
        # (repro.serve). Per-path-then-add pins the grouping; the psum
        # after this is still the pair's one attention-phase sync.
        o2 = o.reshape(B, S, 2, dims.hq * dims.hd)
        wo = p["wo"].astype(o.dtype)
        y = o2[:, :, 0] @ wo[0] + o2[:, :, 1] @ wo[1]
    else:
        y = o.reshape(B, S, dims.hq * dims.hd) @ p["wo"].astype(o.dtype)
    if p.get("bo") is not None:
        bo = p["bo"].astype(jnp.float32)
        if pair:
            bo = bo.sum(axis=0)  # both paths' biases enter the one reduction
        y = y + (bo / dims.tp).astype(y.dtype)
    return y
