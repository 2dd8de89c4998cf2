"""Serving correctness: prefill+decode == full recompute, per family."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from _helpers import run_multidevice

from repro.configs import ASSIGNED_ARCHS, get_config, reduced_config
from repro.core.lp import plan_range
from repro.model import transformer as T
from repro.parallel.context import ParallelContext
from repro.serve import ServeConfig, generate

PC = ParallelContext()


def _setup(arch, lp=True):
    cfg = reduced_config(get_config(arch), n_layers=4 if arch != "recurrentgemma-9b" else 6)
    if cfg.moe_experts:  # capacity drops would break exact prefill/decode equality
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    plan = plan_range(cfg, 0, cfg.n_layers) if lp else None
    ms = T.build_structure(cfg, plan=plan, tp=1)
    params = T.init_params(ms, jax.random.PRNGKey(0))
    extras = {}
    if cfg.prefix_len:
        extras["prefix"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(5), (2, cfg.prefix_len, cfg.d_model))
    if cfg.enc_layers:
        extras["frames"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(6), (2, cfg.enc_seq, cfg.d_model))
    return cfg, ms, params, extras


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_decode_matches_full_forward(arch):
    cfg, ms, params, extras = _setup(arch)
    S = 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, cfg.vocab_size)
    S_tot = S + (cfg.prefix_len or 0)
    pl_logits, caches = T.prefill(params, toks, ms=ms, pc=PC,
                                  max_len=S_tot + 4,
                                  prefix_embed=extras.get("prefix"),
                                  enc_frames=extras.get("frames"),
                                  cache_dtype=jnp.float32)
    full, _, _ = T.forward_full(params, toks, ms=ms, pc=PC,
                                prefix_embed=extras.get("prefix"),
                                enc_frames=extras.get("frames"))
    assert jnp.allclose(pl_logits, full[:, -1], atol=2e-3), \
        f"{arch} prefill mismatch {float(jnp.abs(pl_logits - full[:, -1]).max())}"

    nxt = jnp.argmax(pl_logits, -1).astype(jnp.int32)
    d_logits, _ = T.decode_step(params, nxt, caches, jnp.int32(S_tot),
                                ms=ms, pc=PC)
    toks2 = jnp.concatenate([toks, nxt[:, None]], 1)
    full2, _, _ = T.forward_full(params, toks2, ms=ms, pc=PC,
                                 prefix_embed=extras.get("prefix"),
                                 enc_frames=extras.get("frames"))
    err = float(jnp.abs(d_logits - full2[:, -1]).max())
    assert err < 2e-3, f"{arch} decode mismatch {err}"


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "falcon-mamba-7b",
                                  "recurrentgemma-9b"])
def test_generate_greedy_matches_recompute(arch):
    cfg, ms, params, extras = _setup(arch)
    sv = ServeConfig(max_len=48, temperature=0.0, cache_dtype=jnp.float32)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                 cfg.vocab_size)
    out = generate(params, prompts, 6, ms=ms, pc=PC, sv=sv,
                   prefix=extras.get("prefix"), frames=extras.get("frames"))
    toks = prompts
    for _ in range(6):
        lg, _, _ = T.forward_full(params, toks, ms=ms, pc=PC,
                                  prefix_embed=extras.get("prefix"),
                                  enc_frames=extras.get("frames"))
        toks = jnp.concatenate(
            [toks, jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]], 1)
    assert bool((toks[:, 8:] == out).all()), arch


def test_ring_buffer_window_decode():
    """Sliding-window cache reuses a ring: decoding past the window must
    match the full recompute."""
    cfg = reduced_config(get_config("recurrentgemma-9b"), n_layers=3)
    ms = T.build_structure(cfg, tp=1)
    params = T.init_params(ms, jax.random.PRNGKey(0))
    W = cfg.window
    S = W + 4  # prompt longer than the window
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0, cfg.vocab_size)
    _, caches = T.prefill(params, toks, ms=ms, pc=PC, max_len=S + 8,
                          cache_dtype=jnp.float32)
    nxt = jnp.array([7], jnp.int32)
    d_logits, _ = T.decode_step(params, nxt, caches, jnp.int32(S), ms=ms, pc=PC)
    toks2 = jnp.concatenate([toks, nxt[:, None]], 1)
    full2, _, _ = T.forward_full(params, toks2, ms=ms, pc=PC)
    assert jnp.allclose(d_logits, full2[:, -1], atol=2e-3)


def test_temperature_sampling_valid():
    cfg, ms, params, extras = _setup("tinyllama-1.1b")
    sv = ServeConfig(max_len=32, temperature=1.0, cache_dtype=jnp.float32)
    prompts = jax.random.randint(jax.random.PRNGKey(3), (4, 8), 0,
                                 cfg.vocab_size)
    out = generate(params, prompts, 8, ms=ms, pc=PC, sv=sv,
                   key=jax.random.PRNGKey(11))
    assert out.shape == (4, 8)
    assert bool(((out >= 0) & (out < cfg.vocab_size)).all())
    out2 = generate(params, prompts, 8, ms=ms, pc=PC, sv=sv,
                    key=jax.random.PRNGKey(11))
    assert bool((out == out2).all()), "sampling must be key-deterministic"


def test_sampling_key_sensitivity():
    """generate() with temperature > 0: same key => identical tokens,
    different keys => the sequences differ somewhere."""
    cfg, ms, params, _ = _setup("tinyllama-1.1b")
    sv = ServeConfig(max_len=40, temperature=1.0, cache_dtype=jnp.float32)
    prompts = jax.random.randint(jax.random.PRNGKey(4), (4, 8), 0,
                                 cfg.vocab_size)
    a = generate(params, prompts, 12, ms=ms, pc=PC, sv=sv,
                 key=jax.random.PRNGKey(21))
    a2 = generate(params, prompts, 12, ms=ms, pc=PC, sv=sv,
                  key=jax.random.PRNGKey(21))
    b = generate(params, prompts, 12, ms=ms, pc=PC, sv=sv,
                 key=jax.random.PRNGKey(22))
    assert bool((a == a2).all())
    assert not bool((a == b).all()), \
        "different keys must change at least one sampled token"


@pytest.mark.slow
def test_vocab_parallel_sample_matches_gather_reference():
    """Gumbel-max over the SHARDED vocabulary == gathering the full logits
    and sampling on one device (each rank's gumbels reproduced by folding
    the key with its rank index)."""
    out = run_multidevice(r"""
import jax, jax.numpy as jnp, json
from jax.sharding import PartitionSpec as P
from repro.model import embedding as E
from repro.parallel.context import make_context

tp = 8
mesh = jax.make_mesh((1, tp), ("data", "model"))
pc = make_context(mesh)
B, V = 4, 64
key = jax.random.PRNGKey(7)
logits = jax.random.normal(jax.random.PRNGKey(3), (B, V), jnp.float32) * 3.0
temp = 0.7

fn = jax.shard_map(lambda lg: E.vocab_parallel_sample(lg, key, temp, pc),
               mesh=mesh, in_specs=(P(None, "model"),), out_specs=P(None),
               check_vma=False)
toks = jax.jit(fn)(logits)

# Gather-then-sample reference: concatenate the per-rank gumbel draws
# (key folded with the rank) into the full-vocab noise vector, then argmax.
Vl = V // tp
g = jnp.concatenate([jax.random.gumbel(jax.random.fold_in(key, r), (B, Vl),
                                       jnp.float32) for r in range(tp)], -1)
ref = jnp.argmax(logits / temp + g, axis=-1).astype(jnp.int32)
print("RESULT " + json.dumps({"toks": toks.tolist(), "ref": ref.tolist()}))
""")
    res = json.loads([l for l in out.splitlines()
                      if l.startswith("RESULT")][0][7:])
    assert res["toks"] == res["ref"], res
