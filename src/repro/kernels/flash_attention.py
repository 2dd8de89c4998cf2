"""Flash attention (online-softmax) Pallas kernel.

TPU adaptation of the paper's inference workloads: the prefill/train
attention is compute-bound on the MXU, so the kernel tiles (bq x bk) score
blocks through VMEM with fp32 running (m, l, acc) statistics in scratch —
HBM traffic is O(S*hd) instead of O(S^2).

Grid: (BH, nq, nk) with the kv index innermost; TPU grid iteration is
sequential over the last axis, so the scratch carry implements the online
softmax across kv tiles of one q tile. Supports causal / sliding-window /
chunked / prefix-LM masks via absolute-position arithmetic (the same
tile_mask semantics as the XLA path in repro.model.attention).

Mask kinds are compile-time constants; fully-masked tiles still run (a
future scalar-prefetch skip is noted in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import resolve_interpret

NEG_INF = -1e30


def _tile_mask(kind, qpos, kpos, *, window, chunk, prefix_len):
    q = qpos[:, None]
    k = kpos[None, :]
    if kind == "bidir":
        return jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    m = k <= q
    if kind == "causal":
        if prefix_len:
            m = m | (k < prefix_len)
        return m
    if kind == "window":
        return m & (q - k < window)
    if kind == "chunk":
        return m & (q // chunk == k // chunk)
    raise ValueError(kind)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            kind, window, chunk, prefix_len, q0, k0, bq, bk, nk, scale,
            q_group, k_limit):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)                    # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                    # [bk, hd]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # GQA folding: q rows are [position, group] interleaved (row = s*g + h),
    # so g query heads of one kv head share a kernel invocation and each
    # cache tile is read once for the whole group.
    qpos = q0 + (i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq,), 0)) // q_group
    kpos = k0 + j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk,), 0)
    mask = _tile_mask(kind, qpos, kpos, window=window, chunk=chunk,
                      prefix_len=prefix_len)
    mask = mask & (kpos < k_limit)[None, :]  # kv padding columns
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=-1)
    acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(j == nk - 1)
    def _out():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, kind="causal", window=0, chunk=0,
                    prefix_len=0, q0=0, k0=0, q_group=1, block_q=128,
                    block_k=128, interpret=None):
    """q: [BH, S, hd]; k, v: [BH, T, hd] -> [BH, S, hd].

    ``q_group`` > 1 means q rows are GQA-folded (row = position*g + head);
    masks use position = row // g. S and T are padded to tile multiples;
    padded kv columns are masked via ``k_limit``.
    """
    BH, S, hd = q.shape
    T = k.shape[1]
    bq = min(block_q, S)
    bk = min(block_k, T)
    pad_q = (-S) % bq
    pad_k = (-T) % bk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v
    Sp, Tp = S + pad_q, T + pad_k
    nq, nk = Sp // bq, Tp // bk
    kern = functools.partial(
        _kernel, kind=kind, window=window, chunk=chunk, prefix_len=prefix_len,
        q0=q0, k0=k0, bq=bq, bk=bk, nk=nk, scale=hd ** -0.5,
        q_group=q_group, k_limit=k0 + T)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((BH, Sp, hd), q.dtype),
        grid=(BH, nq, nk),
        in_specs=[pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(qp, kp, vp)
    return out[:, :S] if pad_q else out
