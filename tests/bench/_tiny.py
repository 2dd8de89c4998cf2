"""A tiny cell on the CPU: the benchmark's harness driven end to end at a
size a test run holds (4 layers, d_model 64, one LP pair), from files
written into a temporary directory laid out like ``bench/``."""
from __future__ import annotations

import json
import os
import sys

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "bench")
sys.path.insert(0, os.path.abspath(BENCH))

MODEL = {"d_model": 64, "n_layers": 4, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256,
         "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
         "tie_embeddings": False, "dtype": "bfloat16", "lp_pairs": [[1, 2]]}
#: The tiny cell's limit on the widest gap, in sigma: the program read
#: 0.005-0.038 over 140-160 checked tokens on three seeds here, its fp8
#: control 0.43-0.76.
LIMIT = 0.15

TRAFFIC = {
    "chat": {"kind": "open_loop", "base_seed": 5, "rate_per_s": 20.0,
             "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                        "min": 4, "max": 40},
             "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                        "min": 2, "max": 16},
             "preroll_s": 0.5, "drain_s": 10, "check_requests": 4},
    "sessions": {"kind": "sessions", "base_seed": 6, "rate_per_s": 10.0,
                 "system_prompts": 3, "system_len": 16, "zipf_s": 1.0,
                 "turns": 3,
                 "user": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                          "min": 3, "max": 8},
                 "answer": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                            "min": 2, "max": 8},
                 "think_s": 0.1, "preroll_s": 0.5, "drain_s": 10,
                 "check_requests": 4},
    "longdoc": {"kind": "backlog", "base_seed": 7,
                "prompt": {"dist": "loguniform", "min": 16, "max": 40},
                "output": {"dist": "loguniform", "min": 4, "max": 16},
                "per_phase": 30, "preroll_s": 0.5, "drain_s": 10,
                "check_requests": 3},
}


def write(root: str) -> str:
    """Lay out a tiny benchmark under ``root``; returns its bench dir."""
    here = os.path.join(root, "bench")
    for d in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(here, d), exist_ok=True)
    over = {k: MODEL[k] for k in ("d_model", "n_layers", "n_heads",
                                  "n_kv_heads", "head_dim", "d_ff",
                                  "vocab_size", "rope_theta")}
    cfg = {"name": "tiny", "program_arch": "yi-6b",
           "program_overrides": over, "model": MODEL,
           "serve": {"n_slots": 4, "page_size": 8, "max_len": 64,
                     "n_pages": 64, "prefill_token_budget": 64,
                     "prefix_cache": True}}
    with open(os.path.join(here, "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    cells = []
    for name, t in TRAFFIC.items():
        with open(os.path.join(here, "traffic", f"{name}.json"), "w") as f:
            json.dump(t, f)
        with open(os.path.join(here, "cells", f"tiny.{name}.json"), "w") as f:
            json.dump({"gap_limit_sigma": LIMIT}, f)
        cells.append({"name": f"tiny.{name}", "config": "tiny",
                      "traffic": name, "chips": 1, "why": "test"})
    lat = ["tiny.chat", "tiny.sessions"]
    bench = {
        "workloads": cells,
        "end_to_end": [
            {"name": "ttft_p90_ms", "unit": "ms", "workloads": lat},
            {"name": "itl_p99_ms", "unit": "ms", "workloads": lat},
            {"name": "output_tok_per_s", "unit": "tokens/s",
             "workloads": ["tiny.longdoc"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "queue_wait_p90_ms", "unit": "ms",
             "moves": "ttft_p90_ms", "workloads": lat},
            {"name": "prefix_hit_share", "unit": "%",
             "moves": "ttft_p90_ms", "workloads": ["tiny.sessions"]},
            {"name": "decode_step_ms.tail", "unit": "ms",
             "moves": "itl_p99_ms", "workloads": lat},
            {"name": "device_idle_share.tail", "unit": "%",
             "moves": "itl_p99_ms", "workloads": lat},
            {"name": "decode_occupancy", "unit": "%",
             "moves": "output_tok_per_s", "workloads": ["tiny.longdoc"]}]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return here


def execute(root: str, cell: str, *, seed: int = 2 ** 33 + 5,
            seconds: float = 2.0, trace: bool = False, control=False):
    """One tiny run through ``run.execute`` on the CPU, without touching
    the persistent compilation cache."""
    import jax
    import run
    import spec
    from repro.launch import compile_cache
    here = write(root)
    res = spec.resolve(cell, spec.benchmark(root), here)
    keep = (compile_cache.enable,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    compile_cache.enable = lambda: None
    try:
        return run.execute(res, seed, seconds, trace, jax.devices(),
                           control=control)
    finally:
        compile_cache.enable = keep[0]
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          keep[1])
