"""Layer and LP-pair application.

A ``Group`` is the unit the stack scans over: either one layer or an LP pair
of two consecutive layers. The pair path implements the paper's Fig. 2b
computational-graph rewrite:

    a = x + A_k(LN1_k x) + A_{k+1}(LN1_{k+1} x)     # ONE phase_out
    y = a + F_k(LN2_k a) + F_{k+1}(LN2_{k+1} a)     # ONE phase_out

(for mamba/rec mixers the generalised residual-pair form). Pair params are
the two layers' params stacked on a leading axis — the retraining-free merge
of repro.core.lp is exactly that stacking.

Decode fast path
----------------
Decode (seq=1) is where the paper's speedup lives, and it is latency-bound:
per-layer kernel launches and cache reads dominate, not FLOPs. A pair whose
two halves share one mixer kind therefore stores its KV/state caches
STACKED-CONTIGUOUS on a leading pair axis — ``k``/``v``: [2, B, L, Hkv, hd]
(bare names; per-layer fallback entries keep indexed names ``k0``/``k1``) —
and ``apply_group_decode`` runs the whole pair as ONE call into
``attention.decode_attn_standard(pair=True)`` (or the seq-sharded variant):
one stacked QKV projection, one cache write per tensor, one attention
core / Pallas launch (repro.kernels.decode_attention.decode_attention_pair)
and one merged output projection per phase. Heterogeneous pairs (llama4
chunked+global: different ring lengths) keep the per-half loop. Cross
-attention and mamba/rec pairs use the same stacked storage and a single
stacked application.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, LayerSpec
from repro.model import attention as A
from repro.model import mlp as M
from repro.model import moe as MOE
from repro.model import rglru as RG
from repro.model import ssm as SSM
from repro.model.norms import apply_norm, dual_norm
from repro.model.params import PD
from repro.parallel.context import ParallelContext


@dataclass(frozen=True)
class Group:
    pair: bool
    specs: Tuple[LayerSpec, ...]      # 1 or 2 entries
    layer_ids: Tuple[int, ...]

    @property
    def signature(self):
        return (self.pair, self.specs)


# ---------------------------------------------------------------------------
# Templates
# ---------------------------------------------------------------------------

def _norm_tmpl(cfg):
    t = {"scale": PD((cfg.d_model,), P(),
                     init="zeros" if cfg.norm_plus_one else "ones")}
    if cfg.norm_kind == "layernorm":
        t["bias"] = PD((cfg.d_model,), P(), init="zeros")
    return t


def layer_template(cfg: ArchConfig, spec: LayerSpec, tp: int):
    t: Dict[str, Any] = {}
    if spec.mixer.startswith("attn"):
        t["ln1"] = _norm_tmpl(cfg)
        t["attn"] = A.attn_template(cfg, tp)
    elif spec.mixer == "rec":
        t["ln1"] = _norm_tmpl(cfg)
        t["rec"] = RG.rglru_template(cfg, tp)
    elif spec.mixer == "mamba":
        t["ln1"] = _norm_tmpl(cfg)
        t["mamba"] = SSM.ssm_template(cfg, tp)
    else:
        raise ValueError(spec.mixer)
    if spec.cross_attn:
        t["lnx"] = _norm_tmpl(cfg)
        t["xattn"] = A.attn_template(cfg, tp, cross=True)
    if spec.ffn == "mlp":
        t["ln2"] = _norm_tmpl(cfg)
        t["mlp"] = M.mlp_template(cfg, tp)
    elif spec.ffn == "moe":
        t["ln2"] = _norm_tmpl(cfg)
        t["moe"] = MOE.moe_template(cfg, tp)
    return t


# ---------------------------------------------------------------------------
# Phase runners
# ---------------------------------------------------------------------------

def _norm_inputs(gp, key, x, cfg, group: Group):
    """Per-path normalised inputs: [B,S,D] (single) or [2,B,S,D] (pair)."""
    if group.pair:
        a, b = dual_norm(x, jax.tree.map(lambda v: v[0], gp[key]),
                         jax.tree.map(lambda v: v[1], gp[key]), cfg)
        return jnp.stack([a, b])
    return apply_norm(x, gp[key], cfg)


def _mixer_kinds(group: Group):
    return tuple(s.mixer for s in group.specs)


def _mixer_scope(mixer: str) -> str:
    """Named scope of a group's temporal-mixing phase (norm, mixer and
    residual add): ``attn`` for every attention kind, else the mixer."""
    return "attn" if mixer.startswith("attn") else mixer


def pair_cache_stacked(group: Group) -> bool:
    """True when the group's decode caches use the stacked-contiguous pair
    layout ([2, ...], bare key names) and the fused pair decode path. Pairs
    with heterogeneous mixer kinds (llama4 chunked+global) fall back to
    per-layer entries: their ring lengths/slots differ."""
    return group.pair and len(set(_mixer_kinds(group))) == 1


def attention_phase_full(gp, xn, cfg, dims, pc, *, group: Group, positions,
                         prefix_len=0, cross_kv=None, attn_impl="auto",
                         ctx_kv=None, q0=0):
    """Full-sequence attention (train/prefill). Returns (partial_out, kv_list)
    with one (k, v) in stored layout per layer in the group.

    ``cross_kv`` (whisper decoder): precomputed encoder k/v in FOLDED layout
    [B,T,P*hkv,hd]; q comes from xn, keys are never roped (attn_bidir).

    ``ctx_kv`` (suffix prefill — repro.serve prefix sharing): this group's
    cached CONTEXT keys/values for absolute positions [0, q0), in stored
    layout ({"k"/"v"}: [2,B,Tc,hkv,hd] stacked pair, {"k0"/"v0"}:
    [B,Tc,hkv,hd] single layer; keys already roped when cached). They are
    prepended to the suffix's freshly projected kv so every suffix row
    reduces over exactly ``q0 + S`` keys — the same reduction shape the
    full-prompt forward gives that row, which is what keeps a prefix-hit
    prefill BIT-IDENTICAL to the cold one. ``q0`` is the absolute position
    of the first query row (0 for a full forward).
    """
    kinds = _mixer_kinds(group)
    cross = cross_kv is not None
    p = gp["xattn"] if cross else gp["attn"]
    homogeneous = len(set(kinds)) == 1 or cross
    B = xn.shape[1] if group.pair else xn.shape[0]
    S = xn.shape[2] if group.pair else xn.shape[1]
    nP = 2 if group.pair else 1
    Hk, g = A.core_layout(dims)

    if homogeneous:
        kind = "attn_bidir" if cross else kinds[0]
        q = A.project_q(p, xn, cfg, dims, positions=positions, kind=kind,
                        pair=group.pair)
        if cross:
            k, v = cross_kv
        else:
            k, v = A.project_kv(p, xn, cfg, dims, positions=positions,
                                kind=kind, pair=group.pair)
        ks, vs = _sel_pairwise(k, v, dims, pc, pair=group.pair)
        if ctx_kv is not None:
            cks, cvs = _fold_ctx_kv(ctx_kv, dims, pc, group=group)
            ks = jnp.concatenate([cks.astype(ks.dtype), ks], axis=1)
            vs = jnp.concatenate([cvs.astype(vs.dtype), vs], axis=1)
            if getattr(q0, "ndim", 0) > 0:
                # Per-row ctx lengths (bucketed radix-suffix rows): row i's
                # REAL context is its first q0[i] positions of the
                # Tc-padded ctx block; the rest is garbage-page filler.
                # Rearrange each row's key axis to [real ctx | suffix |
                # junk] so every real key sits at its absolute position —
                # junk lands at kpos >= q0[i] + S, PAST the row's last
                # query (q0[i] + S - 1), where the ordinary causal mask
                # kills it. With the pinned-kv-tile chunked core the junk
                # columns are then bit-transparent exactly like bucket
                # padding (finite masked lanes contribute exact zeros), so
                # a ctx row reduces identically to the cold full-prompt
                # program and a ctx-less row (q0[i] = 0, all-junk tail) is
                # bit-identical to the plain bucket program.
                assert attn_impl.startswith("chunked:"), (
                    "per-row ctx lengths require the pinned-tile chunked "
                    f"attention impl, got {attn_impl!r}")
                Tc, Tt = cks.shape[1], ks.shape[1]
                j = jnp.arange(Tt)[None, :]
                c = q0[:, None]
                idx = jnp.where(j < c, j,
                                jnp.where(j < c + S, Tc + (j - c), j - S))
                ks = jnp.take_along_axis(ks, idx[:, :, None, None], axis=1)
                vs = jnp.take_along_axis(vs, idx[:, :, None, None], axis=1)
            # Materialise the concatenated kv: otherwise XLA splits the
            # value contraction through the concat (p@[v_ctx;v_sfx] ->
            # p1@v_ctx + p2@v_sfx), regrouping the float accumulation and
            # breaking bit-identity with the cold full-prompt forward.
            ks, vs = lax.optimization_barrier((ks, vs))
        qh = q.reshape(B, S, nP * Hk, g, dims.hd)
        with jax.named_scope("attn_core"):
            o = A.attention_core(qh, ks, vs, kind=kind, window=cfg.window,
                                 chunk=cfg.chunk, prefix_len=prefix_len,
                                 q0=q0, impl=attn_impl)
        o = o.reshape(B, S, nP * dims.hq, dims.hd)
        out = A.output_proj(p, o, dims, pair=group.pair)
        return out, _split_kv(k, v, dims, pair=group.pair)

    if ctx_kv is not None:
        raise NotImplementedError(
            "suffix prefill supports homogeneous attention groups only")
    # Heterogeneous pair kinds (llama4 chunked+global): per-half cores, still
    # merged output projection + ONE phase_out.
    os, kvs = [], []
    for i, kind in enumerate(kinds):
        ph = jax.tree.map(lambda w: w[i], p)
        qi, ki, vi = A.project_qkv(ph, xn[i], cfg, dims, pc,
                                   positions=positions, kind=kind, pair=False)
        ksi, vsi = _sel_pairwise(ki, vi, dims, pc, pair=False)
        oi = A.attention_core(qi.reshape(B, S, Hk, g, dims.hd), ksi, vsi,
                              kind=kind, window=cfg.window, chunk=cfg.chunk,
                              prefix_len=prefix_len, impl=attn_impl)
        os.append(oi.reshape(B, S, dims.hq, dims.hd))
        kvs.append((ki, vi))
    o = jnp.concatenate(os, axis=2)
    out = A.output_proj(p, o, dims, pair=True)
    return out, kvs


def _sel_pairwise(k, v, dims, pc, *, pair: bool):
    """Rank-local kv selection, preserving the pair-as-doubled-heads layout."""
    if not pair:
        return A.select_local_kv(k, dims, pc), A.select_local_kv(v, dims, pc)
    B, S = k.shape[0], k.shape[1]
    k2 = k.reshape(B, S, 2, dims.hkv, dims.hd)
    v2 = v.reshape(B, S, 2, dims.hkv, dims.hd)
    if not dims.kv_sharded and dims.tp > 1:
        if dims.per_head:
            idx = A.rank_head_kv_map(dims, pc)
            k2 = jnp.take(k2, idx, axis=3)
            v2 = jnp.take(v2, idx, axis=3)
        else:
            base = pc.tp_index() * dims.hq
            kv_idx = jnp.clip(base // dims.group, 0, dims.hkv - 1)
            k2 = lax.dynamic_slice_in_dim(k2, kv_idx, 1, axis=3)
            v2 = lax.dynamic_slice_in_dim(v2, kv_idx, 1, axis=3)
    ks = k2.reshape(B, S, 2 * k2.shape[3], dims.hd)
    vs = v2.reshape(B, S, 2 * v2.shape[3], dims.hd)
    return ks, vs


def _fold_ctx_kv(ctx_kv, dims, pc, *, group: Group):
    """Cached context kv (stored layout) -> the folded [B,Tc,P*Hk,hd] layout
    ``_sel_pairwise`` produces for fresh projections, so a suffix forward can
    concatenate context before suffix keys along the length axis. Keys in the
    cache are already roped; the pair fold is pair-major, matching
    ``_sel_pairwise``'s [B,S,2,hkv,...] reshape.

    Per-rank branch (tp > 1): a kv-SHARDED pool's ``gather_ctx`` hands each
    rank its LOCAL head shard inside shard_map, so the fold is the identity
    on the head axis; a REPLICATED pool (n_kv < tp) hands every rank all
    stored heads, and the rank in-gathers its own head(s) here — the same
    selection the paged decode kernel performs in-kernel via
    ``paged_head_map``. Either way the folded head count must equal
    ``core_layout``'s per-rank count — audited at trace time so a
    mis-sharded ctx tree fails loudly instead of reducing at a different
    shape than the cold full-prompt program (bit-identity is the
    contract)."""
    Hk_eff, _ = A.core_layout(dims)
    if pair_cache_stacked(group):
        ck, cv = ctx_kv["k"], ctx_kv["v"]              # [2,B,Tc,hkv,hd]
        if dims.kv_sharded or dims.tp == 1:
            ks, vs = ck, cv                            # already rank-local
        else:
            ks = A.select_local_kv_pair(ck, dims, pc)  # in-gather this rank
            vs = A.select_local_kv_pair(cv, dims, pc)
        assert ks.shape[3] == Hk_eff, (
            f"ctx kv folds to {ks.shape[3]} heads per pair half but the "
            f"attention core reduces over {Hk_eff}: the gathered ctx tree "
            "does not match this rank's kv layout")
        B, Tc, Hk = ks.shape[1], ks.shape[2], ks.shape[3]
        ks = jnp.moveaxis(ks, 0, 2).reshape(B, Tc, 2 * Hk, dims.hd)
        vs = jnp.moveaxis(vs, 0, 2).reshape(B, Tc, 2 * Hk, dims.hd)
        return ks, vs
    assert not group.pair, "heterogeneous pairs have no stored ctx layout"
    if dims.kv_sharded or dims.tp == 1:
        ks, vs = ctx_kv["k0"], ctx_kv["v0"]            # [B,Tc,hkv,hd]
    else:
        ks = A.select_local_kv(ctx_kv["k0"], dims, pc)
        vs = A.select_local_kv(ctx_kv["v0"], dims, pc)
    assert ks.shape[2] == Hk_eff, (
        f"ctx kv folds to {ks.shape[2]} heads but the attention core "
        f"reduces over {Hk_eff}: the gathered ctx tree does not match "
        "this rank's kv layout")
    return ks, vs


def _split_kv(k, v, dims, *, pair: bool):
    if not pair:
        return [(k, v)]
    B, S = k.shape[0], k.shape[1]
    k2 = k.reshape(B, S, 2, dims.hkv, dims.hd)
    v2 = v.reshape(B, S, 2, dims.hkv, dims.hd)
    return [(k2[:, :, 0], v2[:, :, 0]), (k2[:, :, 1], v2[:, :, 1])]


def ffn_phase(gp, xn, cfg, pc, *, group: Group):
    """Returns (partial_out, aux)."""
    ffn = group.specs[0].ffn
    if ffn == "mlp":
        return M.mlp_forward(gp["mlp"], xn, cfg, pc.tp_size, pair=group.pair), 0.0
    return MOE.moe_forward(gp["moe"], xn, cfg, pc, pair=group.pair)


# ---------------------------------------------------------------------------
# KV-cache construction
# ---------------------------------------------------------------------------

def ring_len(cfg, mixer: str, max_len: int) -> int:
    if mixer == "attn_local" and cfg.window:
        return min(cfg.window, max_len)
    if mixer == "attn_chunked" and cfg.chunk:
        return min(cfg.chunk, max_len)
    return max_len


def seq_sharded_kind(cfg, dims, mixer: str, kv_mode: str) -> bool:
    """Sequence-shard the cache over the model axis? Only worthwhile for
    full-length causal caches with replicated kv heads."""
    return (kv_mode == "seq" and mixer in ("attn", "attn_global")
            and not dims.kv_sharded and dims.tp > 1)


def fill_cache(k, L: int, *, mixer, cfg, seq_shard: bool, pc, dims):
    """Place prefill keys/values [B,S,hkv,hd] into a decode cache."""
    B, S, H, hd = k.shape
    if mixer == "attn_local" and cfg.window and S >= (W := ring_len(cfg, mixer, L)):
        last = k[:, S - W:]
        return jnp.roll(last, (S - W) % W, axis=1)
    if mixer == "attn_chunked" and cfg.chunk:
        C = ring_len(cfg, mixer, L)
        cstart = (S // C) * C if S % C else S  # S%C==0 -> empty fresh chunk
        ring = jnp.zeros((B, C, H, hd), k.dtype)
        n = S - cstart
        if n:
            ring = lax.dynamic_update_slice_in_dim(ring, k[:, cstart:], 0, axis=1)
        return ring
    Ls = ring_len(cfg, mixer, L)
    pad = jnp.zeros((B, Ls, H, hd), k.dtype)
    kp = lax.dynamic_update_slice_in_dim(pad, k[:, :min(S, Ls)], 0, axis=1)
    if seq_shard:
        L_loc = Ls // dims.tp
        return lax.dynamic_slice_in_dim(kp, pc.tp_index() * L_loc, L_loc, axis=1)
    return kp


def group_cache_meta(cfg, group: Group, dims, *, batch: int, max_len: int,
                     kv_mode: str, enc_len: int = 0, dtype=jnp.bfloat16):
    """(ShapeDtypeStruct tree, PartitionSpec tree) for one group's decode
    cache. Batch axis sharding is added by the caller. Shapes are LOCAL in
    the head/seq dims the model axis shards (shard_map local view) — the
    caller converts to global via pspec rules; here we return GLOBAL shapes
    with their pspecs.

    Homogeneous pairs store STACKED-CONTIGUOUS caches: one entry per tensor
    under a bare name ("k", "v", "xk", "xv", "conv", "h") with a leading
    pair axis of 2, so the fused decode path reads/writes one tensor per
    phase. Per-layer entries keep indexed names ("k0", "k1", ...) — the
    trailing digit is what downstream pspec augmentation keys on."""
    spec_tree, pspec_tree = {}, {}
    stacked = pair_cache_stacked(group)

    def put(name, i, shp, ps, dt):
        if stacked:
            if name not in spec_tree:  # identical for both halves: emit once
                spec_tree[name] = jax.ShapeDtypeStruct((2, *shp), dt)
                pspec_tree[name] = P(None, *ps)
        else:
            spec_tree[f"{name}{i}"] = jax.ShapeDtypeStruct(shp, dt)
            pspec_tree[f"{name}{i}"] = P(*ps)

    for i, spec in enumerate(group.specs):
        m = spec.mixer
        if m.startswith("attn"):
            L = ring_len(cfg, m, max_len)
            shp = (batch, L, dims.hkv_global, dims.hd)
            if seq_sharded_kind(cfg, dims, m, kv_mode):
                ps = (None, "model", None, None)
            elif dims.kv_sharded:
                ps = (None, None, "model", None)
            else:
                ps = (None, None, None, None)
            put("k", i, shp, ps, dtype)
            put("v", i, shp, ps, dtype)
            if spec.cross_attn:
                xshp = (batch, enc_len, dims.hkv_global, dims.hd)
                xps = (None, None, "model", None) if dims.kv_sharded \
                    else (None, None, None, None)
                put("xk", i, xshp, xps, dtype)
                put("xv", i, xshp, xps, dtype)
        elif m == "mamba":
            di = cfg.d_inner
            put("conv", i, (batch, cfg.ssm_conv - 1, di),
                (None, None, "model"), dtype)
            put("h", i, (batch, di, cfg.ssm_state),
                (None, "model", None), jnp.float32)
        elif m == "rec":
            W = cfg.lru_width
            put("conv", i, (batch, cfg.rec_conv - 1, W),
                (None, None, "model"), dtype)
            put("h", i, (batch, W, 1),
                (None, "model", None), jnp.float32)
    return spec_tree, pspec_tree


# ---------------------------------------------------------------------------
# Full-sequence group application (train / prefill)
# ---------------------------------------------------------------------------

def apply_group_full(gp, x, *, cfg, group: Group, dims, pc: ParallelContext,
                     positions, prefix_len=0, enc_out=None, attn_impl="auto",
                     emit_cache=False, max_len=0, kv_mode="heads",
                     scan_impl="chunked", ctx_kv=None, q0=0):
    """One group over the full sequence.

    x: [B,S_local,D] (S_local = S/tp under SP). Returns (x, aux, cache_dict).

    ``ctx_kv``/``q0`` (suffix prefill): cached kv for positions [0, q0) in
    stored layout; the sequence being processed starts at absolute position
    ``q0``. Attention-only (recurrent state cannot resume from kv), and the
    emitted cache covers ONLY the suffix (length ``max_len``, local position
    0 == absolute ``q0``) — the caller owns placing it after the context.
    """
    aux = jnp.float32(0.0)
    cache: Dict[str, Any] = {}
    mixer = group.specs[0].mixer
    nP = 2 if group.pair else 1
    gather_axis = 2 if group.pair else 1

    # ---- phase 1: temporal mixing -------------------------------------
    # Gather-first: under SP the residual is re-gathered BEFORE the norms,
    # so an LP pair's two per-path norms read ONE gathered tensor — half
    # the phase-entry wire bytes of gathering the stacked [2,...] inputs
    # (EXPERIMENTS.md §Perf iteration 2).
    with jax.named_scope(_mixer_scope(mixer)):
        xg = pc.phase_in(x)
        xn = _norm_inputs(gp, "ln1", xg, cfg, group)
        if ctx_kv is not None and not mixer.startswith("attn"):
            raise NotImplementedError(
                "suffix prefill requires attention mixers (recurrent state "
                "cannot resume from cached kv)")
        if mixer.startswith("attn"):
            out, kvs = attention_phase_full(gp, xn, cfg, dims, pc, group=group,
                                            positions=positions,
                                            prefix_len=prefix_len,
                                            attn_impl=attn_impl,
                                            ctx_kv=ctx_kv, q0=q0)
            if emit_cache:
                fks, fvs = [], []
                for i, (k, v) in enumerate(kvs):
                    m = group.specs[i].mixer
                    ss = seq_sharded_kind(cfg, dims, m, kv_mode)
                    fk = fill_cache(k, max_len, mixer=m, cfg=cfg,
                                    seq_shard=ss, pc=pc, dims=dims)
                    fv = fill_cache(v, max_len, mixer=m, cfg=cfg,
                                    seq_shard=ss, pc=pc, dims=dims)
                    if pair_cache_stacked(group):
                        fks.append(fk)
                        fvs.append(fv)
                    else:
                        cache[f"k{i}"], cache[f"v{i}"] = fk, fv
                if fks:  # stacked-contiguous pair layout for the fused decode
                    cache["k"] = jnp.stack(fks)
                    cache["v"] = jnp.stack(fvs)
        else:
            xn_p = xn if group.pair else xn[None]
            key = "mamba" if mixer == "mamba" else "rec"
            mp = (gp[key] if group.pair
                  else jax.tree.map(lambda w: w[None], gp[key]))
            if mixer == "mamba":
                out, state = SSM.ssm_mix(mp, xn_p, cfg, pc, impl=scan_impl)
            else:
                out, state = RG.rglru_mix(mp, xn_p, cfg, pc, impl=scan_impl)
            if emit_cache:
                conv, h = state
                if pair_cache_stacked(group):  # already stacked [2, ...]
                    cache["conv"], cache["h"] = conv, h
                else:
                    for i in range(nP):
                        cache[f"conv{i}"], cache[f"h{i}"] = conv[i], h[i]
        x = x + pc.phase_out(out).astype(x.dtype)

    # ---- cross-attention phase (whisper decoder) ----------------------
    if group.specs[0].cross_attn and enc_out is not None:
        xnx = _norm_inputs(gp, "lnx", pc.phase_in(x), cfg, group)
        enc_in = jnp.stack([enc_out] * 2) if group.pair else enc_out
        xk, xv = A.project_kv(gp["xattn"], enc_in, cfg, dims,
                              positions=None, kind="attn_bidir", pair=group.pair)
        out, _ = attention_phase_full(gp, xnx, cfg, dims, pc, group=group,
                                      positions=positions, cross_kv=(xk, xv),
                                      attn_impl=attn_impl)
        if emit_cache:
            halves = _split_kv(xk, xv, dims, pair=group.pair)
            if pair_cache_stacked(group):
                cache["xk"] = jnp.stack([ki for ki, _ in halves])
                cache["xv"] = jnp.stack([vi for _, vi in halves])
            else:
                for i, (ki, vi) in enumerate(halves):
                    cache[f"xk{i}"], cache[f"xv{i}"] = ki, vi
        x = x + pc.phase_out(out).astype(x.dtype)

    # ---- phase 2: FFN ---------------------------------------------------
    if group.specs[0].ffn is not None:
        with jax.named_scope("mlp"):
            xn2 = _norm_inputs(gp, "ln2", pc.phase_in(x), cfg, group)
            out, a = ffn_phase(gp, xn2, cfg, pc, group=group)
            aux = aux + a
            x = x + pc.phase_out(out).astype(x.dtype)
    return x, aux, cache


# ---------------------------------------------------------------------------
# Single-token decode group application
# ---------------------------------------------------------------------------

def apply_group_decode(gp, x, cache, t, *, cfg, group: Group, dims,
                       pc: ParallelContext, kv_mode="heads",
                       cache_layout="ring", block_tables=None):
    """One group for one new token. x: [B,1,D] (replicated over model; no SP
    at decode). Returns (x, new_cache).

    Stacked pairs (pair_cache_stacked) take the FUSED fast path: the whole
    pair is one decode_attn_*(pair=True) call over the stacked [2, ...]
    cache — one QKV projection, one cache read/write, one attention kernel
    launch and one psum per phase, instead of the per-half loop's two of
    each. Heterogeneous pairs and single layers use the per-half loop.

    cache_layout="paged" (continuous batching): ``t`` is a [B] vector of
    per-slot positions, attention k/v entries are page pools indirected
    through ``block_tables`` [B, n_pg], and state entries stay slot-indexed
    with B == n_slots. The fused pair path is preserved — one
    decode_attn_paged(pair=True) call per stacked pair. Under tp > 1 the
    pool shards kv heads over the model axis exactly like the ring cache
    (replicated when n_kv < tp, with in-kernel head selection); a
    seq-sharded page pool has no block-table analogue, so kv_mode="seq"
    is rejected rather than silently ignored.
    """
    new_cache: Dict[str, Any] = {}
    mixer = group.specs[0].mixer
    nP = 2 if group.pair else 1
    paged = cache_layout == "paged"
    if paged and kv_mode != "heads":
        raise NotImplementedError(
            f"paged decode supports kv_mode='heads' only (got {kv_mode!r}): "
            "pages shard kv heads over the model axis, not the sequence")
    fused = pair_cache_stacked(group)
    if fused:  # tolerate caches emitted under the per-layer layout
        fused = ("k" if mixer.startswith("attn") else "conv") in cache
    if paged and group.pair and mixer.startswith("attn") and not fused:
        raise NotImplementedError(
            "paged decode requires the stacked pair cache layout "
            "(heterogeneous attention pairs are not pageable)")

    with jax.named_scope(_mixer_scope(mixer)):
        xn = _norm_inputs(gp, "ln1", x, cfg, group)
        if mixer.startswith("attn"):
            if paged and fused:
                out, nk, nv = A.decode_attn_paged(
                    gp["attn"], xn, cache["k"], cache["v"], t, block_tables,
                    cfg, dims, pc, kind=mixer, pair=True)
                new_cache["k"], new_cache["v"] = nk, nv
            elif paged:
                o, nk, nv = A.decode_attn_paged(
                    gp["attn"], xn, cache["k0"], cache["v0"], t, block_tables,
                    cfg, dims, pc, kind=mixer, pair=False)
                out = o
                new_cache["k0"], new_cache["v0"] = nk, nv
            elif fused:
                decode_fn = (A.decode_attn_seq_sharded
                             if seq_sharded_kind(cfg, dims, mixer, kv_mode)
                             else A.decode_attn_standard)
                out, nk, nv = decode_fn(
                    gp["attn"], xn, cache["k"], cache["v"], t, cfg, dims, pc,
                    kind=mixer, pair=True, window=cfg.window, chunk=cfg.chunk)
                new_cache["k"], new_cache["v"] = nk, nv
            else:
                outs = []
                for i, spec in enumerate(group.specs):
                    ph = (jax.tree.map(lambda w: w[i], gp["attn"])
                          if group.pair else gp["attn"])
                    xi = xn[i] if group.pair else xn
                    kd = spec.mixer
                    if seq_sharded_kind(cfg, dims, kd, kv_mode):
                        o, nk, nv = A.decode_attn_seq_sharded(
                            ph, xi, cache[f"k{i}"], cache[f"v{i}"], t, cfg,
                            dims, pc, kind=kd, pair=False, window=cfg.window,
                            chunk=cfg.chunk)
                    else:
                        o, nk, nv = A.decode_attn_standard(
                            ph, xi, cache[f"k{i}"], cache[f"v{i}"], t, cfg,
                            dims, pc, kind=kd, pair=False, window=cfg.window,
                            chunk=cfg.chunk)
                    outs.append(o)
                    new_cache[f"k{i}"], new_cache[f"v{i}"] = nk, nv
                out = sum(outs)
        else:
            xn_p = xn if group.pair else xn[None]
            key = "mamba" if mixer == "mamba" else "rec"
            mp = (gp[key] if group.pair
                  else jax.tree.map(lambda w: w[None], gp[key]))
            if fused:  # stacked state: no per-step gather/scatter of halves
                conv, h = cache["conv"], cache["h"]
            else:
                conv = jnp.stack([cache[f"conv{i}"] for i in range(nP)], 0)
                h = jnp.stack([cache[f"h{i}"] for i in range(nP)], axis=0)
            if mixer == "mamba":
                out, (nconv, nh) = SSM.ssm_mix(mp, xn_p, cfg, pc,
                                               state=(conv, h))
            else:
                out, (nconv, nh) = RG.rglru_mix(mp, xn_p, cfg, pc,
                                                state=(conv, h))
            if fused:
                new_cache["conv"], new_cache["h"] = nconv, nh
            else:
                for i in range(nP):
                    new_cache[f"conv{i}"], new_cache[f"h{i}"] = nconv[i], nh[i]
        x = x + pc.psum_tp(out).astype(x.dtype)

    if group.specs[0].cross_attn and ("xk" in cache or "xk0" in cache):
        xnx = _norm_inputs(gp, "lnx", x, cfg, group)
        Hk, g = A.core_layout(dims)
        if "xk" in cache:
            # Fused pair cross-attention: one stacked q projection, one core
            # call with the pair folded into the head axis, one merged
            # output projection -> the psum below is the phase's ONE sync.
            q = A.project_q(gp["xattn"], xnx, cfg, dims, positions=None,
                            kind="attn_bidir", pair=True)   # [B,1,2*hq,hd]
            B = q.shape[0]
            ks = A.select_local_kv_pair(cache["xk"], dims, pc)  # [2,B,T,Hk,hd]
            vs = A.select_local_kv_pair(cache["xv"], dims, pc)
            T = ks.shape[2]
            ksf = jnp.moveaxis(ks, 0, 2).reshape(B, T, 2 * Hk, dims.hd)
            vsf = jnp.moveaxis(vs, 0, 2).reshape(B, T, 2 * Hk, dims.hd)
            o = A.attention_core(q.reshape(B, 1, 2 * Hk, g, dims.hd),
                                 ksf, vsf, kind="attn_bidir", impl="dense")
            o = o.reshape(B, 1, 2 * dims.hq, dims.hd)
            out = A.output_proj(gp["xattn"], o, dims, pair=True)
            new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
        else:
            outs = []
            for i in range(nP):
                ph = jax.tree.map(lambda w: w[i], gp["xattn"]) if group.pair else gp["xattn"]
                xi = xnx[i] if group.pair else xnx
                q = A.project_q(ph, xi, cfg, dims, positions=None,
                                kind="attn_bidir", pair=False)
                ks = A.select_local_kv(cache[f"xk{i}"], dims, pc)
                vs = A.select_local_kv(cache[f"xv{i}"], dims, pc)
                B = q.shape[0]
                o = A.attention_core(q.reshape(B, 1, Hk, g, dims.hd), ks, vs,
                                     kind="attn_bidir", impl="dense")
                o = o.reshape(B, 1, dims.hq, dims.hd)
                outs.append(A.output_proj(ph, o, dims, pair=False))
                new_cache[f"xk{i}"], new_cache[f"xv{i}"] = cache[f"xk{i}"], cache[f"xv{i}"]
            out = sum(outs)
        x = x + pc.psum_tp(out).astype(x.dtype)

    if group.specs[0].ffn is not None:
        with jax.named_scope("mlp"):
            xn2 = _norm_inputs(gp, "ln2", x, cfg, group)
            out, _ = ffn_phase(gp, xn2, cfg, pc, group=group)
            x = x + pc.psum_tp(out).astype(x.dtype)
    return x, new_cache
