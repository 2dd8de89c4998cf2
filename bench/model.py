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

import spec
import weights as W

#: Model keys of every configuration file and the program's ArchConfig
#: field each must equal; each layer part checks the keys it reads.
ARCH_FIELDS = {"d_model": "d_model", "n_layers": "n_layers",
               "vocab_size": "vocab_size", "tie_embeddings": "tie_embeddings"}


def arch_config(c: dict):
    """The program's ArchConfig for configuration ``c``, checked against
    the configuration's model keys: the shared ones, the layer pattern
    (each layer's parts against the program's layer kinds) and the keys
    each part relies on."""
    from repro.configs import get_config
    cfg = get_config(c["program_arch"])
    if c.get("program_overrides"):
        cfg = dataclasses.replace(cfg, **c["program_overrides"])
    m = c["model"]
    bad = {k: (m[k], getattr(cfg, f)) for k, f in ARCH_FIELDS.items()
           if m[k] != getattr(cfg, f)}
    if cfg.norm_kind != "rmsnorm":
        bad["norm_kind"] = ("rmsnorm", cfg.norm_kind)
    for i, s in enumerate(cfg.layer_specs()):
        kind = spec.layer_kind(m, i)
        mixer, *ffn = spec.parts_of(kind)
        want = (mixer.KIND, ffn[0].KIND if ffn else None, False)
        if want != (s.mixer, s.ffn, s.cross_attn):
            bad[f"layer {i}"] = (kind, (s.mixer, s.ffn, s.cross_attn))
    for part in {p for kind in spec.pattern(m) for p in spec.parts_of(kind)}:
        bad.update(part.check(cfg, m))
    if bad:
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
