"""Distributed trainer: ZeRO-sharded AdamW train step, built once as a LOCAL
function and run either directly (CPU unit tests) or inside shard_map over
the production mesh (launch/train.py, launch/dryrun.py).

State layout
------------
  params : model-dtype tree, GLOBAL shapes. Two leaf families:
           * regular leaves — tp-sharded via the param pspecs, replicated
             over dp; optimizer state is flat ZeRO shards (dp, tp, chunk)
           * FSDP leaves (ms.fsdp segments) — flat (count, data, tp, chunk)
             shards; the forward all-gathers one group at a time and AD
             reduce-scatters the grads (repro.parallel.fsdp)
  master : fp32 master weights; same flat layouts
  m, v   : AdamW moments, like master
  step   : int32 scalar
  err    : optional int8-compression error feedback (compress_pod)

Collective schedule per step (the distributed-optimization tricks):
  * grads for tp-REPLICATED leaves: one psum over `model`
  * regular-leaf ZeRO reduction: hierarchical psum_scatter — exact over the
    intra-pod `data` axis, optionally int8+error-feedback compressed over
    the cross-pod `pod` (DCI) axis
  * FSDP-leaf grads: reduce_scatter over `data` comes out of AD; cross-pod
    one psum (optionally compressed)
  * global-norm clip: one scalar psum
  * fresh forward params: one all_gather over dp for regular leaves; FSDP
    leaves stay flat (gathers happen per group inside the forward)
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.model import transformer as T
from repro.model.params import abstract_tree, init_tree, is_pd, pspec_tree
from repro.parallel import zero
from repro.parallel.compress import compress_psum
from repro.parallel.context import ParallelContext
from repro.train.optimizer import OptConfig, adamw_update, schedule_lr

PyTree = Any


@dataclass(frozen=True)
class TrainConfig:
    opt: OptConfig = OptConfig()
    accum: int = 1                 # gradient-accumulation microbatches
    remat: bool = False            # activation checkpointing per group
    param_dtype: Any = jnp.float32  # bf16 on TPU
    compress_pod: bool = False     # int8+EF gradient compression on `pod`
    finetune_lp_only: bool = False  # paper Table 2: train LP segments only
    aux_weight: float = 1e-2
    attn_impl: str = "auto"
    scan_impl: str = "chunked"


# ---------------------------------------------------------------------------
# Leaf metadata (regular vs FSDP)
# ---------------------------------------------------------------------------

def _local_shape(shape, pspec, tp: int):
    out = []
    for i, dim in enumerate(shape):
        ax = pspec[i] if i < len(pspec) else None
        names = ax if isinstance(ax, tuple) else ((ax,) if ax else ())
        f = 1
        for nm in names:
            f *= tp if nm == "model" else 1
        assert dim % f == 0, (shape, pspec, tp)
        out.append(dim // f)
    return tuple(out)


def _tp_sharded(pspec) -> bool:
    for ax in pspec:
        names = ax if isinstance(ax, tuple) else ((ax,) if ax else ())
        if "model" in names:
            return True
    return False


def _chunk(shape, pspec, pc: ParallelContext) -> int:
    n = 1
    for d in _local_shape(shape, pspec, pc.tp_size):
        n *= d
    return -(-n // pc.dp_size)


def _sharded_dim(pspec) -> Optional[int]:
    for i, ax in enumerate(pspec):
        names = ax if isinstance(ax, tuple) else ((ax,) if ax else ())
        if "model" in names:
            return i
    return None


@dataclass(frozen=True)
class LeafInfo:
    pd: Any                 # PD descriptor (of the STORED layout)
    pspec: Any
    wd: float               # weight-decay mask
    tp_sharded: bool        # distinct values across the model axis?
    fsdp: bool


def _leaf_meta(ms: T.ModelStructure):
    """(template, treedef, [LeafInfo]) in flattened order."""
    tmpl = T.model_template(ms)
    leaves, treedef = jax.tree.flatten(tmpl, is_leaf=is_pd)

    wd_t = jax.tree.map(lambda pd: 1.0 if len(pd.shape) >= 2 else 0.0,
                        tmpl, is_leaf=is_pd)
    tpf_t = jax.tree.map(lambda pd: _tp_sharded(pd.pspec), tmpl, is_leaf=is_pd)
    ff_t = jax.tree.map(lambda pd: False, tmpl, is_leaf=is_pd)
    if ms.fsdp:
        metas = T.segment_metas(ms)
        wd_t["segments"] = [m.treedef.unflatten(list(m.wd_flags)) for m in metas]
        tpf_t["segments"] = [m.treedef.unflatten(list(m.tp_flags)) for m in metas]
        ff_t["segments"] = [jax.tree.map(lambda pd: True, st, is_leaf=is_pd)
                            for st in tmpl["segments"]]
    infos = [
        LeafInfo(pd, pd.pspec, wd, tpf, ff)
        for pd, wd, tpf, ff in zip(
            leaves, treedef.flatten_up_to(wd_t), treedef.flatten_up_to(tpf_t),
            treedef.flatten_up_to(ff_t))
    ]
    return tmpl, treedef, infos


# ---------------------------------------------------------------------------
# Flat-state packing for REGULAR leaves
# ---------------------------------------------------------------------------

def to_flat_global(x, pspec, pc: ParallelContext):
    """GLOBAL param tensor -> GLOBAL flat state leaf (dp, tp, chunk)."""
    tp, dp = pc.tp_size, pc.dp_size
    d = _sharded_dim(pspec)
    if d is None:
        locs = jnp.broadcast_to(x.reshape(1, -1), (tp, x.size))
    else:
        s = x.shape[d]
        locs = jnp.moveaxis(
            x.reshape(*x.shape[:d], tp, s // tp, *x.shape[d + 1:]), d, 0
        ).reshape(tp, -1)
    n = locs.shape[1]
    pad = (-n) % dp
    if pad:
        locs = jnp.pad(locs, ((0, 0), (0, pad)))
    return locs.reshape(tp, dp, -1).transpose(1, 0, 2).astype(jnp.float32)


def from_flat_global(flat, shape, pspec, pc: ParallelContext, dtype=jnp.float32):
    """Inverse of ``to_flat_global`` (mesh-agnostic checkpoint path)."""
    tp = pc.tp_size
    d = _sharded_dim(pspec)
    loc_shape = _local_shape(shape, pspec, tp)
    n = 1
    for s in loc_shape:
        n *= s
    locs = flat.transpose(1, 0, 2).reshape(tp, -1)[:, :n]
    if d is None:
        return locs[0].reshape(shape).astype(dtype)
    parts = locs.reshape(tp, *loc_shape)
    out = jnp.moveaxis(parts, 0, d)
    return out.reshape(shape).astype(dtype)


def _pod_data(pc: ParallelContext) -> Tuple[int, int]:
    if "pod" not in pc.dp_axes:
        return 1, pc.dp_size
    return pc.pod_size, pc.dp_size // pc.pod_size


# ---------------------------------------------------------------------------
# State construction
# ---------------------------------------------------------------------------

def init_state(ms: T.ModelStructure, key, pc: ParallelContext,
               tc: TrainConfig) -> Dict[str, Any]:
    """GLOBAL train state (pure function of key — jit with out_shardings to
    materialise sharded on a mesh)."""
    tmpl, treedef, infos = _leaf_meta(ms)
    params32 = T.init_params(ms, key, jnp.float32)  # FSDP leaves pre-packed
    flat_p = treedef.flatten_up_to(params32)
    master = treedef.unflatten([
        x if li.fsdp else to_flat_global(x, li.pspec, pc)
        for x, li in zip(flat_p, infos)])
    state = {
        "params": jax.tree.map(lambda x: x.astype(tc.param_dtype), params32),
        "master": master,
        "m": jax.tree.map(jnp.zeros_like, master),
        "v": jax.tree.map(jnp.zeros_like, master),
        "step": jnp.zeros((), jnp.int32),
    }
    if tc.compress_pod:
        state["err"] = _err_init(ms, pc, tc)
    return state


def _err_shape(li: LeafInfo, pc: ParallelContext):
    pod, _ = _pod_data(pc)
    if li.fsdp:
        return li.pd.shape  # (count, data, tp, chunk) — same layout
    return (pc.dp_size, pc.tp_size, pod, _chunk(li.pd.shape, li.pspec, pc))


def _err_init(ms, pc, tc):
    _, treedef, infos = _leaf_meta(ms)
    return treedef.unflatten(
        [jnp.zeros(_err_shape(li, pc), jnp.float32) for li in infos])


def _err_pspec(li: LeafInfo, pc: ParallelContext):
    dp = tuple(pc.dp_axes) if pc.dp_axes else (None,)
    dp_ax = dp if len(dp) > 1 else dp[0]
    if li.fsdp:
        return li.pspec
    return P(dp_ax, "model", None, None)


def state_pspecs(ms: T.ModelStructure, pc: ParallelContext,
                 tc: TrainConfig) -> Dict[str, Any]:
    tmpl, treedef, infos = _leaf_meta(ms)
    dp = tuple(pc.dp_axes) if pc.dp_axes else (None,)
    dp_ax = dp if len(dp) > 1 else dp[0]
    flat_spec = treedef.unflatten([
        li.pspec if li.fsdp else P(dp_ax, "model", None) for li in infos])
    out = {
        "params": pspec_tree(tmpl),
        "master": flat_spec,
        "m": flat_spec,
        "v": jax.tree.map(lambda x: x, flat_spec),
        "step": P(),
    }
    if tc.compress_pod:
        out["err"] = treedef.unflatten([_err_pspec(li, pc) for li in infos])
    return out


def abstract_state(ms: T.ModelStructure, pc: ParallelContext,
                   tc: TrainConfig) -> Dict[str, Any]:
    tmpl, treedef, infos = _leaf_meta(ms)
    flat = treedef.unflatten([
        jax.ShapeDtypeStruct(
            li.pd.shape if li.fsdp else
            (pc.dp_size, pc.tp_size, _chunk(li.pd.shape, li.pspec, pc)),
            jnp.float32)
        for li in infos])
    out = {
        "params": abstract_tree(tmpl, tc.param_dtype),
        "master": flat,
        "m": jax.tree.map(lambda x: x, flat),
        "v": jax.tree.map(lambda x: x, flat),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if tc.compress_pod:
        out["err"] = treedef.unflatten([
            jax.ShapeDtypeStruct(_err_shape(li, pc), jnp.float32)
            for li in infos])
    return out


# ---------------------------------------------------------------------------
# Gradient reduction for REGULAR leaves (hierarchical, pod-compressed)
# ---------------------------------------------------------------------------

def _reduce_grads(g32, err, pc: ParallelContext, tc: TrainConfig):
    """Local fp32 grad leaf -> this rank's mean-grad flat shard (chunk,)."""
    dp = pc.dp_size
    flat = zero.flatten_leaf(g32, dp)  # (dp, chunk)
    if dp == 1:
        return flat[0], err
    pod, data = _pod_data(pc)
    if pod == 1 or not tc.compress_pod:
        return pc.psum_scatter_dp(flat, axis=0)[0] / dp, err
    chunk = flat.shape[1]
    f3 = flat.reshape(pod, data, chunk)
    s1 = lax.psum_scatter(f3, "data", scatter_dimension=1, tiled=True)
    s1 = s1.reshape(pod, chunk)
    s2, new_err = compress_psum(s1, ("pod",), err)
    row = lax.axis_index("pod")
    shard = lax.dynamic_index_in_dim(s2, row, axis=0, keepdims=False)
    return shard / dp, new_err


def _reduce_grads_fsdp(g32, err, li: LeafInfo, pc: ParallelContext,
                       tc: TrainConfig):
    """FSDP leaf: AD already reduce-scattered over `data`; finish the mean
    across `pod` (and sync tp-replicated leaves)."""
    if not li.tp_sharded:
        g32 = pc.psum_tp(g32)
    pod, _ = _pod_data(pc)
    if pod > 1:
        if tc.compress_pod:
            g32, err = compress_psum(g32, ("pod",), err)
        else:
            g32 = lax.psum(g32, "pod")
    return g32 / pc.dp_size, err


# ---------------------------------------------------------------------------
# The train step (local function — identical under shard_map and on CPU)
# ---------------------------------------------------------------------------

def make_train_step(ms: T.ModelStructure, pc: ParallelContext, tc: TrainConfig):
    tmpl, treedef, infos = _leaf_meta(ms)
    ft_mask = None
    if tc.finetune_lp_only:
        # Paper Table 2: only the LP-paired segments are trainable.
        full = jax.tree.map(lambda pd: 0.0, tmpl, is_leaf=is_pd)
        full["segments"] = [
            jax.tree.map(lambda pd: 1.0 if seg.group.pair else 0.0, st,
                         is_leaf=is_pd)
            for st, seg in zip(tmpl["segments"], ms.segments)]
        ft_mask = treedef.flatten_up_to(full)

    def loss_of(params, micro):
        return T.loss_fn(params, micro, ms=ms, pc=pc, remat=tc.remat,
                         attn_impl=tc.attn_impl, scan_impl=tc.scan_impl,
                         aux_weight=tc.aux_weight)

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def compute_grads(params, batch):
        if tc.accum == 1:
            (loss, parts), grads = grad_fn(params, batch)
            return loss, parts, jax.tree.map(
                lambda g: g.astype(jnp.float32), grads)

        def micro_of(i):
            return jax.tree.map(
                lambda x: x.reshape(tc.accum, x.shape[0] // tc.accum,
                                    *x.shape[1:])[i], batch)

        def body(carry, i):
            acc, loss_sum = carry
            (loss, parts), grads = grad_fn(params, micro_of(i))
            acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / tc.accum, acc, grads)
            return (acc, loss_sum + loss / tc.accum), parts

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss), parts = lax.scan(body, (zeros, jnp.float32(0.0)),
                                        jnp.arange(tc.accum))
        parts = jax.tree.map(lambda x: x.mean(), parts)
        return loss, parts, grads

    pod, _ = _pod_data(pc)

    def step_fn(state, batch):
        params = state["params"]
        loss, parts, grads = compute_grads(params, batch)

        flat_g = treedef.flatten_up_to(grads)
        if ft_mask is not None:
            flat_g = [g * m for g, m in zip(flat_g, ft_mask)]

        errs = (treedef.flatten_up_to(state["err"]) if "err" in state
                else [None] * len(flat_g))
        shards, new_errs = [], []
        for g, e, li in zip(flat_g, errs, infos):
            if li.fsdp:
                # local grad view (count, 1, 1, chunk); err same layout
                s, ne = _reduce_grads_fsdp(g, e, li, pc, tc)
            else:
                if not li.tp_sharded:
                    g = pc.psum_tp(g)
                e0 = e[0, 0] if e is not None else None
                s, ne = _reduce_grads(g, e0, pc, tc)
                if ne is not None:
                    ne = ne[None, None]
            shards.append(s)
            new_errs.append(ne)

        # Global grad-norm: shards partition over (data x leaves); fsdp
        # leaves are pod-replicated (divide by pod); tp-sharded leaves need
        # the model-axis psum, replicated ones must count once.
        sq_sh = jnp.float32(0.0)
        sq_rp = jnp.float32(0.0)
        for s, li in zip(shards, infos):
            contrib = jnp.sum(jnp.square(s))
            if li.fsdp:
                contrib = contrib / pod
            if li.tp_sharded:
                sq_sh = sq_sh + contrib
            else:
                sq_rp = sq_rp + contrib
        sq = pc.psum_dp(pc.psum_tp(sq_sh) + sq_rp)
        gnorm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, tc.opt.grad_clip / jnp.maximum(gnorm, 1e-12))

        lr = schedule_lr(tc.opt, state["step"])
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        flat_p = treedef.flatten_up_to(state["master"])
        flat_like = treedef.flatten_up_to(params)
        new_p, new_m, new_v, new_params = [], [], [], []
        for g, m_, v_, p_, li, like in zip(
                shards, flat_m, flat_v, flat_p, infos, flat_like):
            if li.fsdp:
                np_, nm, nv = adamw_update(g * scale, m_, v_, p_,
                                           state["step"], tc.opt, lr=lr,
                                           wd_mask=li.wd)
                new_p.append(np_)
                new_m.append(nm)
                new_v.append(nv)
                new_params.append(np_.astype(tc.param_dtype))
            else:
                m0, v0, p0 = m_[0, 0], v_[0, 0], p_[0, 0]
                np_, nm, nv = adamw_update(g * scale, m0, v0, p0,
                                           state["step"], tc.opt, lr=lr,
                                           wd_mask=li.wd)
                new_p.append(np_[None, None])
                new_m.append(nm[None, None])
                new_v.append(nv[None, None])
                # Fresh forward tensor: ONE all_gather over dp. ``like`` is
                # the rank-LOCAL view, so reshape straight back to it.
                full = pc.all_gather_dp(np_[None, :], axis=0)
                new_params.append(full.reshape(-1)[:like.size]
                                  .reshape(like.shape).astype(tc.param_dtype))

        new_state = {
            "params": treedef.unflatten(new_params),
            "master": treedef.unflatten(new_p),
            "m": treedef.unflatten(new_m),
            "v": treedef.unflatten(new_v),
            "step": state["step"] + 1,
        }
        if "err" in state:
            new_state["err"] = treedef.unflatten(new_errs)
        metrics = {
            "loss": pc.pmean_dp(loss),
            "xent": pc.pmean_dp(parts["xent"]),
            "grad_norm": gnorm,
            "lr": lr,
        }
        return new_state, metrics

    return step_fn


def state_from_params(params32, ms: T.ModelStructure, pc: ParallelContext,
                      tc: TrainConfig) -> Dict[str, Any]:
    """Fresh optimizer state around EXISTING fp32 params (e.g. an LP-converted
    pretrained model about to be recovery-fine-tuned, paper Table 2)."""
    tmpl, treedef, infos = _leaf_meta(ms)
    flat_p = treedef.flatten_up_to(params32)
    master = treedef.unflatten([
        x.astype(jnp.float32) if li.fsdp else to_flat_global(x, li.pspec, pc)
        for x, li in zip(flat_p, infos)])
    state = {
        "params": jax.tree.map(lambda x: x.astype(tc.param_dtype), params32),
        "master": master,
        "m": jax.tree.map(jnp.zeros_like, master),
        "v": jax.tree.map(jnp.zeros_like, master),
        "step": jnp.zeros((), jnp.int32),
    }
    if tc.compress_pod:
        state["err"] = _err_init(ms, pc, tc)
    return state


def make_eval_step(ms: T.ModelStructure, pc: ParallelContext, tc: TrainConfig):
    def eval_fn(params, batch):
        loss, parts = T.loss_fn(params, batch, ms=ms, pc=pc,
                                attn_impl=tc.attn_impl, scan_impl=tc.scan_impl,
                                aux_weight=tc.aux_weight)
        return {"loss": pc.pmean_dp(loss), "xent": pc.pmean_dp(parts["xent"])}
    return eval_fn


# ---------------------------------------------------------------------------
# Sharded wrappers
# ---------------------------------------------------------------------------

def batch_pspecs(pc: ParallelContext, batch_tree):
    dp = tuple(pc.dp_axes) if pc.dp_axes else (None,)
    dp_ax = dp if len(dp) > 1 else dp[0]
    return jax.tree.map(lambda x: P(dp_ax, *([None] * (x.ndim - 1))), batch_tree)


def make_sharded_train_step(ms: T.ModelStructure, mesh, tc: TrainConfig,
                            batch_abstract, *, sp: bool = True, donate=True):
    """jit(shard_map(train_step)) over the production mesh.

    Returns (jitted_fn, state_pspec_tree, batch_pspec_tree, pc).
    """
    from repro.parallel.context import make_context

    pc = make_context(mesh, sp=sp)
    local = make_train_step(ms, pc, tc)
    s_specs = state_pspecs(ms, pc, tc)
    b_specs = batch_pspecs(pc, batch_abstract)
    wrapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(s_specs, b_specs),
        out_specs=(s_specs, {"loss": P(), "xent": P(), "grad_norm": P(),
                             "lr": P()}),
        check_vma=False)
    jitted = jax.jit(wrapped, donate_argnums=(0,) if donate else ())
    return jitted, s_specs, b_specs, pc
