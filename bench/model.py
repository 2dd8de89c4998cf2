"""The system under test, built from a benchmark configuration file.

``build(cfg_file, seed)`` makes the program's model structure, hands it
the seed's weights (``weights.program_params``) and builds the serving
engine the way ``python -m repro.launch.serve --continuous --full-config``
does: ``PagedEngine`` over ``PagedServeConfig`` with the automatic bucket
ladder, greedy sampling, bf16 weights and cache, and
the program's default decode and norm implementations (no switch is set
here). The geometry (slots, pages, ``max_len``), whether the radix
prefix cache is on (``--prefix-cache``/``--no-prefix-cache`` there) and
the LP plan come from the configuration file.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

import weights as W

#: Model keys of a configuration file and the program's ArchConfig field
#: each must equal.
ARCH_FIELDS = {"d_model": "d_model", "n_layers": "n_layers",
               "n_heads": "n_heads", "n_kv_heads": "n_kv_heads",
               "head_dim": "head_dim", "d_ff": "d_ff",
               "vocab_size": "vocab_size", "rope_theta": "rope_theta",
               "tie_embeddings": "tie_embeddings"}


def arch_config(c: dict):
    """The program's ArchConfig for configuration ``c``, checked against
    the configuration's model keys."""
    from repro.configs import get_config
    cfg = get_config(c["program_arch"])
    if c.get("program_overrides"):
        cfg = dataclasses.replace(cfg, **c["program_overrides"])
    m = c["model"]
    bad = {k: (m[k], getattr(cfg, f)) for k, f in ARCH_FIELDS.items()
           if m[k] != getattr(cfg, f)}
    if bad or cfg.norm_kind != "rmsnorm" or not cfg.mlp_gated \
            or cfg.mlp_act != "silu" or cfg.pos_embed != "rope":
        raise ValueError(f"{c['name']}: the program's {cfg.name} differs "
                         f"from the configuration: {bad}")
    return cfg


def serve_config(c: dict):
    from repro.serve import PagedServeConfig
    s = c["serve"]
    return PagedServeConfig(
        n_slots=s["n_slots"], page_size=s["page_size"],
        n_pages=s["n_pages"], max_len=s["max_len"],
        prefill_token_budget=s["prefill_token_budget"],
        prefix_cache=s["prefix_cache"],
        cache_dtype=jnp.dtype(c["model"]["dtype"]))


def structure(c: dict):
    from repro.core.lp import LPPlan
    from repro.model import transformer as T
    plan = LPPlan(tuple(tuple(p) for p in c["model"]["lp_pairs"]))
    return T.build_structure(arch_config(c), plan=plan, tp=1)


def segments_of(ms):
    return [(s.count, s.group.pair, s.group.layer_ids[0])
            for s in ms.segments]


def build(c: dict, seed: int):
    """(engine, model structure) for configuration ``c``."""
    from repro.model import transformer as T
    from repro.serve import PagedEngine
    ms = structure(c)
    dt = jnp.dtype(c["model"]["dtype"])
    params = W.program_params(T.abstract_params(ms, dt), segments_of(ms),
                              c["model"], seed, dt)
    return PagedEngine(params, ms, serve_config(c)), ms
