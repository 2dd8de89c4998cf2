"""Full model assembly: embedding -> (encoder) -> LP-grouped stack -> head.

One ``ModelStructure`` describes everything static (config, LP plan, TP
degree, scan segments); the functional entry points are:

  loss_fn        — token cross-entropy for train_step
  forward_full   — logits over a full sequence (train fwd / prefill)
  prefill        — forward_full + KV/state cache emission
  decode_step    — one new token against the cache (serve_step)

All functions run identically on a single CPU device (pc=ParallelContext())
and inside shard_map over a 512-chip mesh — collectives degrade to identity
when the axis is absent (repro.parallel.context).

Family handling:
  encdec (whisper)  — encoder consumes precomputed frame embeddings (the
                      conv frontend is a stub per the assignment); the
                      decoder cross-attends to the encoder output.
  vlm (paligemma)   — precomputed SigLIP patch embeddings are prepended to
                      the token embeddings as a bidirectional prefix
                      (prefix-LM mask via cfg.prefix_len).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, LayerSpec
from repro.core.lp import EMPTY_PLAN, LPPlan
from repro.model import attention as A
from repro.model import blocks as B
from repro.model import embedding as E
from repro.model import stack as ST
from repro.model.norms import apply_norm
from repro.model.params import PD, abstract_tree, init_tree, pspec_tree
from repro.parallel.context import ParallelContext

PyTree = Any


# ---------------------------------------------------------------------------
# Structure
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelStructure:
    cfg: ArchConfig
    plan: LPPlan
    tp: int
    segments: Tuple[ST.Segment, ...]
    enc_segments: Tuple[ST.Segment, ...] = ()
    fsdp: bool = False        # ZeRO-3 flat segment params over "data"
    fsdp_data: int = 1        # size of the FSDP (intra-pod data) axis
    quant: bool = False       # int8 FSDP weight shards (serving only)

    @property
    def dims(self) -> A.AttnDims:
        return A.attn_dims(self.cfg, self.tp)

    @property
    def effective_depth(self) -> int:
        return self.plan.effective_depth(self.cfg.n_layers)


def build_structure(cfg: ArchConfig, *, plan: Optional[LPPlan] = None,
                    tp: int = 1, fsdp: bool = False,
                    fsdp_data: int = 1, quant: bool = False) -> ModelStructure:
    plan = plan or EMPTY_PLAN
    groups = ST.make_groups(cfg, plan.pairs)
    segments = tuple(ST.make_segments(groups))
    enc_segments: Tuple[ST.Segment, ...] = ()
    if cfg.enc_layers:
        enc_spec = LayerSpec(mixer="attn_bidir", ffn="mlp")
        enc_groups = ST.make_groups(cfg, (), specs=[enc_spec] * cfg.enc_layers)
        enc_segments = tuple(ST.make_segments(enc_groups))
    if quant:
        assert fsdp, "int8 weight shards require FSDP layout"
    return ModelStructure(cfg, plan, tp, segments, enc_segments, fsdp,
                          fsdp_data, quant)


def segment_metas(ms: ModelStructure):
    """FSDP flat-layout metadata per decoder segment."""
    from repro.parallel import fsdp as F
    return [F.segment_meta(ST.group_template(ms.cfg, seg.group, ms.tp),
                           seg.count, tp=ms.tp, data=ms.fsdp_data)
            for seg in ms.segments]


def model_template(ms: ModelStructure) -> Dict[str, Any]:
    cfg, tp = ms.cfg, ms.tp
    if ms.fsdp:
        from repro.parallel import fsdp as F
        seg_tmpl = [F.flat_segment_pds(meta, data=ms.fsdp_data, tp=tp)
                    for meta in segment_metas(ms)]
        if ms.quant:
            from repro.model.params import PD as _PD
            def q_pds(tree):
                qt = jax.tree.map(lambda pd: _PD(pd.shape, pd.pspec,
                                                 init="zeros",
                                                 dtype=jnp.int8), tree)
                st = jax.tree.map(lambda pd: _PD(
                    (*pd.shape[:-1], -(-pd.shape[-1] // F.QBLOCK)),
                    pd.pspec, init="zeros", dtype=jnp.float32), tree)
                return {"q": qt, "scale": st}
            seg_tmpl = [q_pds(t) for t in seg_tmpl]
    else:
        seg_tmpl = ST.stack_template(cfg, ms.segments, tp)
    t: Dict[str, Any] = {
        "embed": E.embed_template(cfg, tp),
        "segments": seg_tmpl,
        "final_norm": B._norm_tmpl(cfg),
    }
    if ms.enc_segments:
        t["enc_segments"] = ST.stack_template(cfg, ms.enc_segments, tp)
        t["enc_norm"] = B._norm_tmpl(cfg)
    return t


def init_params(ms: ModelStructure, key, dtype=jnp.float32,
                mesh=None) -> PyTree:
    """Random parameters from ``key``. ``mesh``: make every leaf already
    sharded by ``param_pspecs(ms)`` on it (serving under tp > 1)."""
    if not ms.fsdp:
        return init_tree(model_template(ms), key, dtype, mesh=mesh)
    assert mesh is None, "FSDP params are packed on the host"
    # FSDP: init the REGULAR template (correct fan-in scaling), then pack.
    from repro.parallel import fsdp as F
    reg = build_structure(ms.cfg, plan=ms.plan, tp=ms.tp)
    params = init_tree(model_template(reg), key, dtype)
    metas = segment_metas(ms)
    packed = []
    for sp, seg, meta in zip(params["segments"], ms.segments, metas):
        groups = ([jax.tree.map(lambda v: v[i], sp) for i in range(seg.count)]
                  if seg.count > 1 else [sp])
        flat = F.pack_segment(groups, meta, data=ms.fsdp_data,
                              tp=ms.tp, dtype=dtype)
        packed.append(F.quantize_segment(flat) if ms.quant else flat)
    params["segments"] = packed
    return params


def stack_params_and_gathers(params, ms: ModelStructure, pc: ParallelContext):
    """(segment param trees, gather_fns) for the stack apply. FSDP leaves
    arrive as the rank-local (count, 1, 1, chunk) view -> (count, chunk)."""
    if not ms.fsdp:
        return params["segments"], None
    from repro.parallel import fsdp as F
    metas = segment_metas(ms)
    segs = [jax.tree.map(lambda v: v.reshape(v.shape[0], v.shape[-1]), sp)
            for sp in params["segments"]]
    if ms.quant:
        gathers = [F.make_gather_fn_q(meta, pc) for meta in metas]
    else:
        gathers = [F.make_gather_fn(meta, pc) for meta in metas]
    return segs, gathers


def param_pspecs(ms: ModelStructure) -> PyTree:
    return pspec_tree(model_template(ms))


def abstract_params(ms: ModelStructure, dtype=jnp.bfloat16) -> PyTree:
    return abstract_tree(model_template(ms), dtype)


def param_count(ms: ModelStructure) -> int:
    leaves = jax.tree.leaves(abstract_params(ms))
    return sum(int(jnp.prod(jnp.array(l.shape))) for l in leaves)


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg, pc: ParallelContext, *, positions):
    """Token ids -> full [B,S,D] residual stream (one psum, vocab-parallel)."""
    x = E.embed_lookup(params["embed"], tokens, pc)
    x = pc.psum_tp(x)
    x = E.add_positions(params["embed"], x, positions)
    if cfg.norm_plus_one:  # gemma-style sqrt(D) embedding scale
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _head(params, x, cfg, pc: ParallelContext):
    """Final norm + column-parallel LM head -> LOCAL logits [..., V/tp]."""
    x = apply_norm(x, params["final_norm"], cfg)
    return E.local_logits(params["embed"], x, cfg, pc)


def _encoder(params, frames, ms: ModelStructure, pc: ParallelContext,
             *, attn_impl="auto"):
    """Whisper encoder on precomputed frame embeddings [B,T,D] (stub
    frontend). Runs without SP so the output is full-sequence on every rank
    (cross-attention projects K/V from it)."""
    enc_pc = pc.with_sp(False)
    pos = jnp.arange(frames.shape[1])[None, :]
    h, _, _ = ST.apply_stack_full(params["enc_segments"], frames,
                                  ms.enc_segments, cfg=ms.cfg, dims=ms.dims,
                                  pc=enc_pc, positions=pos, attn_impl=attn_impl)
    return apply_norm(h, params["enc_norm"], ms.cfg)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def forward_full(params, tokens, *, ms: ModelStructure, pc: ParallelContext,
                 prefix_embed=None, enc_frames=None, emit_cache=False,
                 max_len=0, kv_mode="heads", remat=False, attn_impl="auto",
                 scan_impl="chunked", cache_dtype=jnp.bfloat16,
                 ctx_kv=None, start=0):
    """tokens: [B, S_text] -> (local_logits [B, S_total, V/tp], aux, caches).

    prefix_embed (vlm): [B, P, D] patch embeddings prepended to the stream.
    enc_frames (encdec): [B, T, D] frame embeddings for the encoder.

    ctx_kv/start (suffix prefill — repro.serve prefix sharing): process
    ``tokens`` as the SUFFIX of a stream whose first ``start`` positions
    have cached kv in ``ctx_kv`` (one count-stacked tree per segment, the
    layer layout of the emitted caches). Every suffix row attends over
    exactly ``start + S`` keys — the reduction shape the full-prompt
    forward gives the same row, which keeps suffix prefill bit-identical
    to cold prefill. ``start`` may be a [B] array of PER-ROW context
    lengths (bucketed radix-hit prefill: each row's suffix begins at its
    own ctx length; requires the pinned-tile chunked ``attn_impl``).
    Attention-only; the emitted cache covers only the
    suffix (length ``max_len``, local 0 == absolute ``start``).
    """
    cfg = ms.cfg
    Bt, S_text = tokens.shape
    prefix_len = cfg.prefix_len if prefix_embed is not None else 0
    if ctx_kv is not None:
        assert prefix_len == 0 and enc_frames is None, \
            "suffix prefill does not compose with prefix-LM/encoder inputs"
    S = S_text + prefix_len
    if getattr(start, "ndim", 0) > 0:
        # Per-row suffix offsets (bucketed radix-hit prefill): row i's
        # suffix begins at its own ctx length. A bare broadcast would
        # mis-align [B] against the length axis, so shape it explicitly.
        positions = start[:, None] + jnp.arange(S)[None, :]
    else:
        positions = start + jnp.arange(S)[None, :]

    x = _embed(params, tokens, cfg, pc,
               positions=positions[:, prefix_len:])
    if prefix_embed is not None:
        x = jnp.concatenate([prefix_embed.astype(x.dtype), x], axis=1)

    enc_out = None
    if enc_frames is not None:
        enc_out = _encoder(params, enc_frames, ms, pc, attn_impl=attn_impl)

    x = pc.shard_seq(x)
    seg_params, gather_fns = stack_params_and_gathers(params, ms, pc)
    x, aux, caches = ST.apply_stack_full(
        seg_params, x, ms.segments, cfg=cfg, dims=ms.dims, pc=pc,
        positions=positions, prefix_len=prefix_len, enc_out=enc_out,
        attn_impl=attn_impl, emit_cache=emit_cache,
        max_len=max_len or S, kv_mode=kv_mode, remat=remat,
        scan_impl=scan_impl, gather_fns=gather_fns, ctx=ctx_kv, q0=start)
    x = pc.phase_in(x)  # SP: re-gather the sequence before the LM head
    logits = _head(params, x, cfg, pc)
    return logits, aux, caches


def loss_fn(params, batch, *, ms: ModelStructure, pc: ParallelContext,
            remat=False, attn_impl="auto", scan_impl="chunked",
            aux_weight=1e-2):
    """Mean next-token cross-entropy (+ MoE load-balance aux).

    batch: {"tokens": [B,S], "labels": [B,S]} plus optional "prefix"/"frames".
    labels < 0 are masked out. Loss is averaged over the DP axes by the
    caller's pmean on gradients (each rank computes its local-batch mean).
    """
    logits, aux, _ = forward_full(
        params, batch["tokens"], ms=ms, pc=pc,
        prefix_embed=batch.get("prefix"), enc_frames=batch.get("frames"),
        remat=remat, attn_impl=attn_impl, scan_impl=scan_impl)
    labels = batch["labels"]
    prefix_len = ms.cfg.prefix_len if batch.get("prefix") is not None else 0
    if prefix_len:
        logits = logits[:, prefix_len:]
    mask = (labels >= 0).astype(jnp.float32)
    xent = E.vocab_parallel_xent(logits, jnp.maximum(labels, 0), pc, mask=mask)
    return xent + aux_weight * aux, {"xent": xent, "aux": aux}


# ---------------------------------------------------------------------------
# Prefill + decode (serving)
# ---------------------------------------------------------------------------

def cache_meta(ms: ModelStructure, *, batch: int, max_len: int,
               kv_mode="heads", dtype=jnp.bfloat16):
    """(abstract, pspec) trees for the decode cache (per segment)."""
    return ST.stack_cache_meta(ms.cfg, ms.segments, ms.dims, batch=batch,
                               max_len=max_len, kv_mode=kv_mode,
                               enc_len=ms.cfg.enc_seq if ms.enc_segments else 0,
                               dtype=dtype)


def cache_batch_axis(entry_name: str) -> int:
    """Axis of the BATCH dim in a count-stacked cache entry [count, ...].

    Stacked pair entries (bare names "k", "xv", "conv", ... — see
    blocks.group_cache_meta) carry a leading pair axis of 2, so batch sits
    at axis 2; per-layer entries ("k0", "conv1", ...) keep it at axis 1.
    """
    return 1 if entry_name[-1].isdigit() else 2


def prefill(params, tokens, *, ms: ModelStructure, pc: ParallelContext,
            max_len: int, prefix_embed=None, enc_frames=None,
            kv_mode="heads", attn_impl="auto", cache_dtype=jnp.bfloat16):
    """Returns (last-position local logits [B, V/tp], caches)."""
    logits, _, caches = forward_full(
        params, tokens, ms=ms, pc=pc, prefix_embed=prefix_embed,
        enc_frames=enc_frames, emit_cache=True, max_len=max_len,
        kv_mode=kv_mode, attn_impl=attn_impl, cache_dtype=cache_dtype)
    caches = jax.tree.map(lambda c: c.astype(cache_dtype)
                          if c.dtype in (jnp.float32, jnp.bfloat16) else c,
                          caches)
    return logits[:, -1], caches


def decode_step(params, tok, caches, t, *, ms: ModelStructure,
                pc: ParallelContext, kv_mode="heads", cache_layout="ring",
                block_tables=None):
    """One decode step. tok: [B] int32 ids; t: scalar absolute position of
    ``tok`` in the stream. Returns (local logits [B, V/tp], new caches).

    cache_layout="paged" (continuous batching — repro.serve): ``t`` is a
    [B] int32 VECTOR of per-slot positions, ``caches`` is the paged pool
    tree (serve.paged_cache) and ``block_tables`` [B, n_pg] carries the
    slot -> page indirection. The ring path is untouched. The same body
    runs inside shard_map on a tp > 1 mesh: tok/t/block_tables arrive
    replicated (host-side scheduling is tp-agnostic) and only the pool's
    kv-head axis is sharded (serve.engine.make_sharded_serve_step).
    """
    cfg = ms.cfg
    dpc = pc.with_sp(False)  # decode never uses sequence parallelism
    if cache_layout == "paged":
        assert block_tables is not None
        t = jnp.asarray(t, jnp.int32)
        assert t.ndim == 1, f"paged decode takes per-slot positions, got {t.shape}"
        pos = t[:, None]          # per-slot positions for embed/rope
    else:
        pos = jnp.full((tok.shape[0], 1), t, jnp.int32)
    x = _embed(params, tok[:, None], cfg, dpc, positions=pos)
    seg_params, gather_fns = stack_params_and_gathers(params, ms, dpc)
    x, new_caches = ST.apply_stack_decode(
        seg_params, x, caches, t, ms.segments, cfg=cfg, dims=ms.dims,
        pc=dpc, kv_mode=kv_mode, gather_fns=gather_fns,
        cache_layout=cache_layout, block_tables=block_tables)
    logits = _head(params, x, cfg, dpc)
    return logits[:, 0], new_caches
