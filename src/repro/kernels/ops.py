"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to auto-detection inside each kernel (compiled on
TPU, interpreter on CPU — repro.compat.resolve_interpret) so the same call
sites run everywhere. The model layers call these when their
``*_impl="pallas"`` knobs are set; the XLA fallbacks in repro.model remain
the default for the CPU dry-run.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.decode_attention import decode_attention_pair as _decode_pair
from repro.kernels.decode_attention import (
    decode_attention_paged as _decode_paged,
    decode_attention_pair_paged as _decode_pair_paged,
)
from repro.kernels.dual_rmsnorm import dual_rmsnorm as _dual
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.ssm_scan import ssm_scan as _scan


@partial(jax.jit, static_argnames=("eps", "plus_one", "block_m"))
def dual_rmsnorm(x, sa, sb, *, eps=1e-6, plus_one=False, block_m=128):
    """x: [..., D] -> (ya, yb) with per-path scales (LP pair norms)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    ya, yb = _dual(x2, sa, sb, eps=eps, plus_one=plus_one, block_m=block_m)
    return ya.reshape(shape), yb.reshape(shape)


@partial(jax.jit, static_argnames=("kind", "window", "chunk", "prefix_len",
                                   "q0", "k0", "q_group", "block_q", "block_k"))
def flash_attention(q, k, v, *, kind="causal", window=0, chunk=0,
                    prefix_len=0, q0=0, k0=0, q_group=1, block_q=128,
                    block_k=128):
    """q: [BH, S, hd]; k, v: [BH, T, hd] -> [BH, S, hd]."""
    return _flash(q, k, v, kind=kind, window=window, chunk=chunk,
                  prefix_len=prefix_len, q0=q0, k0=k0, q_group=q_group,
                  block_q=block_q, block_k=block_k)


@partial(jax.jit, static_argnames=("block_l",))
def decode_attention(q, k, v, t_valid, *, block_l=256):
    """q: [B, Hkv, g, hd]; k, v: [B, L, Hkv, hd] -> [B, Hkv, g, hd]."""
    return _decode(q, k, v, t_valid, block_l=block_l)


@partial(jax.jit, static_argnames=("block_l",))
def decode_attention_pair(q, k, v, t_valid, *, block_l=256):
    """Fused LP-pair decode: q [2, B, Hkv, g, hd]; k, v [2, B, L, Hkv, hd]
    (stacked pair cache) -> [2, B, Hkv, g, hd] in ONE kernel launch."""
    return _decode_pair(q, k, v, t_valid, block_l=block_l)


@jax.jit
def decode_attention_paged(q, k_pages, v_pages, block_tables, t_valid,
                           head_map=None):
    """Paged decode: q [B, Hkv, g, hd]; k/v_pages [n_pages, Hkv, ps, hd];
    block_tables [B, n_pg]; t_valid [B]; head_map optional [Hkv] local ->
    stored kv-head selection (replicated-kv TP) -> [B, Hkv, g, hd]."""
    return _decode_paged(q, k_pages, v_pages, block_tables, t_valid,
                         head_map=head_map)


@jax.jit
def decode_attention_pair_paged(q, k_pages, v_pages, block_tables, t_valid,
                                head_map=None):
    """Fused paged LP-pair decode: q [2, B, Hkv, g, hd]; k/v_pages
    [2, n_pages, Hkv, ps, hd]; one shared block table (and one optional
    head_map) for both halves -> [2, B, Hkv, g, hd] in ONE kernel launch."""
    return _decode_pair_paged(q, k_pages, v_pages, block_tables, t_valid,
                              head_map=head_map)


@partial(jax.jit, static_argnames=("block_s", "block_c"))
def ssm_scan(a, b, h0, *, block_s=256, block_c=128):
    """Selective scan: (y, hT) for h_t = a_t h_{t-1} + b_t."""
    return _scan(a, b, h0, block_s=block_s, block_c=block_c)
