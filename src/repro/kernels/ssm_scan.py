"""Chunked selective-scan Pallas kernel (Mamba-1 / RG-LRU recurrence).

h_t = a_t * h_{t-1} + b_t, per (channel, state) element. The GPU Mamba
kernel uses warp-level shuffles; the TPU adaptation reorganises the same
work-efficient scan around VMEM tiles: within a (seq-chunk x channel-tile)
block the prefix is computed with an in-register associative scan (log-depth
on the VPU), and the carry h crosses seq chunks through VMEM scratch while
the grid walks the sequence axis sequentially.

Grid: (B, nC, nS) with S innermost ("arbitrary" = sequential), so the
scratch carry is live exactly for one (batch, channel-tile) stripe.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import resolve_interpret


def _combine(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a1 * a2, a2 * b1 + b2


def _kernel(a_ref, b_ref, h0_ref, y_ref, hT_ref, h_sc, *, ns):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        h_sc[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)                    # [bs, bc, N]
    b = b_ref[0].astype(jnp.float32)
    cum_a, cum_b = jax.lax.associative_scan(_combine, (a, b), axis=0)
    y = cum_b + cum_a * h_sc[...][None]
    y_ref[0] = y.astype(y_ref.dtype)
    h_sc[...] = y[-1]

    @pl.when(s == ns - 1)
    def _out():
        hT_ref[0] = h_sc[...].astype(hT_ref.dtype)


def ssm_scan(a, b, h0, *, block_s=256, block_c=128, interpret=None):
    """a, b: [B, S, C, N]; h0: [B, C, N] -> (y [B,S,C,N], hT [B,C,N]).

    S padded to a block multiple with identity elements (a=1, b=0) so the
    carry is unaffected; C padded with zeros.
    """
    B, S, C, N = a.shape
    bs = min(block_s, S)
    bc = min(block_c, C)
    pad_s = (-S) % bs
    pad_c = (-C) % bc
    if pad_s:
        a = jnp.concatenate(
            [a, jnp.ones((B, pad_s, C, N), a.dtype)], axis=1)
        b = jnp.concatenate(
            [b, jnp.zeros((B, pad_s, C, N), b.dtype)], axis=1)
    if pad_c:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pad_c), (0, 0)),
                    constant_values=1)
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad_c), (0, 0)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_c), (0, 0)))
    Sp, Cp = S + pad_s, C + pad_c
    ns, nc = Sp // bs, Cp // bc

    kern = functools.partial(_kernel, ns=ns)
    y, hT = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((B, Sp, Cp, N), a.dtype),
                   jax.ShapeDtypeStruct((B, Cp, N), jnp.float32)),
        grid=(B, nc, ns),
        in_specs=[pl.BlockSpec((1, bs, bc, N), lambda bt, c, s: (bt, s, c, 0)),
                  pl.BlockSpec((1, bs, bc, N), lambda bt, c, s: (bt, s, c, 0)),
                  pl.BlockSpec((1, bc, N), lambda bt, c, s: (bt, c, 0))],
        out_specs=(pl.BlockSpec((1, bs, bc, N), lambda bt, c, s: (bt, s, c, 0)),
                   pl.BlockSpec((1, bc, N), lambda bt, c, s: (bt, c, 0))),
        scratch_shapes=[pltpu.VMEM((bc, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(a, b, h0)
    y = y[:, :S, :C] if (pad_s or pad_c) else y
    hT = hT[:, :C] if pad_c else hT
    return y, hT
