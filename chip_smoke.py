"""Chip smoke: serve Yi-6B at its published widths through the paged LP
engine on a TPU, and check every generated token against a float32
reference of the same model.

    python chip_smoke.py              # one chip: LP on, then LP off
    python chip_smoke.py --chips 4    # only tp=4 on a 1x4 mesh, LP on

The model is Yi-6B (``src/repro/configs/yi_6b.py``) with all 32 layers,
d_model 4096, 32 q heads, 4 kv heads, head_dim 128, d_ff 11008 and vocab
64000. Weights are random bf16, made on the device from ``--seed``. The
engine is the normal serving path (``PagedEngine`` / ``PagedServeConfig``,
as ``python -m repro.launch.serve --continuous --full-config`` builds it):
8 slots, 16-token pages, 1024 tokens per slot, the bucketed prefill ladder
and the radix prefix cache. 16 seeded requests whose prompts span the
ladder, half of them behind one shared page-aligned prefix, each make 32
new tokens.

Phases, one engine at a time, the first one's arrays freed before the
second is built:

- LP on: an effective depth of 25 (seven layer pairs, the paper's kind of
  plan), decode attention through the paged Pallas kernels and the pair
  norms through the dual-RMSNorm kernel. The compiled decode program must
  hold a ``tpu_custom_call``.
- LP off: the plain 32-layer model, XLA decode attention.
- With ``--chips 4`` only: LP on at tp=4 (one kv head and 16000 vocab
  columns per chip), against the reference run under the same mesh.

The check: for each request the model's own cache-free ``forward_full``
runs on prompt + generated tokens with float32 activations, the same
weights and plan, and the highest matmul precision. At every generated
position the reference logit of the engine's token must lie within
``TOL_SIGMA`` standard deviations (of that position's reference logits)
of the reference's largest logit. Tokens are not required to be equal:
with random weights the top two logits are often closer than bf16
rounding, and the argmax flips.

It exits non-zero, and prints no result, unless JAX's first device is a
TPU. It is one process: a child that needed the chip would find it held by
this one. The last line of stdout is the JSON result; the lines before it
are a smoke run's figures, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "yi-6b"
LP_EFF_DEPTH = 25            # 32 layers - 7 pairs
N_SLOTS, PAGE, MAX_LEN = 8, 16, 1024
N_REQUESTS, NEW_TOKENS = 16, 32
SHARED_PREFIX = 256          # 16 whole pages, shared by half the requests
# The engine keeps weights, activations and kv in bf16; the reference runs
# float32 activations over the same bf16 weights. bf16 keeps 8 bits of
# mantissa, so each of the 32 layers' two residual adds carries a relative
# error of ~2^-9, about 0.02 of the logits' own standard deviation (sigma)
# after 64 of them. Where the engine's token is not the reference's argmax,
# the two top logits were closer than that error, so the gap stays a small
# fraction of sigma; 0.5 sigma leaves room for the tail over 512 positions.
# A token read through a wrong page, head or position lands far below the
# top at some of them: the controls in ``reference_gaps`` show it each run.
TOL_SIGMA = 0.5


def make_prompts(seed: int, vocab: int):
    """16 prompts whose lengths spread log-uniformly over the bucket ladder
    (16 .. MAX_LEN - NEW_TOKENS); the odd ones start with one shared
    page-aligned prefix."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, SHARED_PREFIX)
    top = MAX_LEN - NEW_TOKENS
    prompts = []
    for i in range(N_REQUESTS):
        lo = SHARED_PREFIX + PAGE if i % 2 else PAGE
        n = int(np.exp(rng.uniform(np.log(lo), np.log(top))))
        tail = rng.integers(0, vocab, n)
        prompts.append(np.concatenate([shared, tail[SHARED_PREFIX:]])
                       if i % 2 else tail)
    return [p.astype(np.int32) for p in prompts]


class CompileLog:
    """Seconds JAX spent in backend compiles (persistent-cache loads
    included) and the persistent cache's hits and misses."""

    def __init__(self):
        import jax.monitoring as M
        self.seconds, self.hits, self.misses = 0.0, 0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        M.register_event_duration_secs_listener(on_duration)
        M.register_event_listener(on_event)


def require(ok, message):
    """A failed check ends the run (an ``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(message)


def gap_sigma(logits, tokens):
    """How far below each row's top logit the logit of ``tokens`` lies, in
    units of that row's standard deviation."""
    picked = logits[np.arange(len(tokens)), tokens]
    return (logits.max(-1) - picked) / logits.std(-1)


def reference_gaps(params, ms, mesh, prompts, outputs, seed):
    """``gap_sigma`` of the engine's tokens at every generated position
    against the float32 reference (``engine``: the check itself), and
    against three references made wrong on purpose, each the way a faulty
    engine would be (the controls, which the check must fail):
    ``wrong_position`` scores each token one position late, ``wrong_page``
    reads other tokens in place of the prompt's first page, and
    ``wrong_head`` maps every q head group to the next kv head."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.model import transformer as T
    from repro.parallel.context import ParallelContext, make_context

    vocab, hkv, hd = ms.cfg.vocab_size, ms.dims.hkv_global, ms.dims.hd
    # float32 activations: the embedding table sets the stream's dtype;
    # every matmul casts its bf16 weight to it.
    emb = dict(params["embed"], tok=params["embed"]["tok"].astype(jnp.float32))
    ref_params = dict(params, embed=emb)

    def roll_kv_heads(w):       # on the host, whatever w's sharding
        h = np.asarray(w)
        h = np.roll(h.reshape(*h.shape[:-1], hkv, hd), 1, axis=-2)
        return jax.device_put(h.reshape(w.shape), w.sharding)

    head_params = jax.tree_util.tree_map_with_path(
        lambda path, w: (roll_kv_heads(w) if path[-1].key in ("wk", "wv")
                         else w), ref_params)
    pc = make_context(mesh, sp=False) if mesh is not None else ParallelContext()

    def logits_at(p, toks, pos):
        lg, _, _ = T.forward_full(p, toks, ms=ms, pc=pc)
        return lg[0, pos]                             # [NEW_TOKENS, V/tp]

    if mesh is not None:
        logits_at = jax.shard_map(
            logits_at, mesh=mesh, in_specs=(T.param_pspecs(ms), P(), P()),
            out_specs=P(None, "model"), check_vma=False)
    fn = jax.jit(logits_at)

    def logits(p, seq, pos):
        padded = np.zeros((1, MAX_LEN), np.int32)    # one compiled shape
        padded[0, :len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            lg = fn(p, jnp.asarray(padded), jnp.asarray(pos))
        return np.asarray(lg)[:, :vocab]

    rng = np.random.default_rng(seed + 1)
    out = {k: [] for k in ("engine", "wrong_position", "wrong_page",
                           "wrong_head")}
    for prompt, gen in zip(prompts, outputs):
        seq = np.concatenate([prompt, gen[:-1]])
        pos = len(prompt) - 1 + np.arange(NEW_TOKENS, dtype=np.int32)
        lg = logits(ref_params, seq, pos)
        out["engine"].append(gap_sigma(lg, gen))
        out["wrong_position"].append(gap_sigma(lg[:-1], gen[1:]))
        bad = seq.copy()                 # the last prompt token stays
        n_bad = min(PAGE, len(prompt) - 1)
        bad[:n_bad] = rng.integers(0, vocab, n_bad)
        out["wrong_page"].append(gap_sigma(logits(ref_params, bad, pos), gen))
        out["wrong_head"].append(gap_sigma(logits(head_params, seq, pos),
                                           gen))
    del ref_params, head_params, emb
    return {k: np.concatenate(v) for k, v in out.items()}


def run_phase(name: str, *, lp: bool, mesh, prompts, seed: int, log):
    """Build one engine, serve every prompt, check it; returns a summary
    and frees the phase's device arrays."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.lp import EMPTY_PLAN, plan_for_depth
    from repro.model import attention as A
    from repro.model import norms as N
    from repro.model import transformer as T
    from repro.serve import PagedEngine, PagedServeConfig
    from repro.serve.scheduler import FINISHED

    cfg = get_config(ARCH)
    plan = plan_for_depth(cfg, LP_EFF_DEPTH) if lp else EMPTY_PLAN
    tp = 1 if mesh is None else mesh.shape["model"]
    ms = T.build_structure(cfg, plan=plan, tp=tp)
    psv = PagedServeConfig(
        n_slots=N_SLOTS, page_size=PAGE, max_len=MAX_LEN,
        n_pages=1 + N_SLOTS * MAX_LEN // PAGE, prefix_cache=True)
    impl = "pallas" if lp else "xla"
    A.set_decode_impl(impl)
    N.set_dual_impl(impl)
    try:
        t0 = time.perf_counter()
        c0 = log.seconds
        params = T.init_params(ms, jax.random.PRNGKey(seed), jnp.bfloat16,
                               mesh=mesh)
        eng = PagedEngine(params, ms, psv, mesh=mesh)
        rids = [eng.add_request(p, NEW_TOKENS) for p in prompts]
        res = eng.drain()
        for r in eng.caches + [eng.params]:
            jax.block_until_ready(r)
        serve_s = time.perf_counter() - t0
        kernels = None
        if lp:
            z = jnp.zeros((N_SLOTS,), jnp.int32)
            text = eng._decode.lower(
                eng.params, eng.caches, z, z, jnp.asarray(eng.block_tables),
                jnp.zeros((N_SLOTS,), jnp.bool_),
                jax.random.PRNGKey(0)).compile().as_text()
            kernels = text.count("tpu_custom_call")
            require(kernels, "the LP decode program holds no Pallas kernel")
    finally:
        A.set_decode_impl("xla")
        N.set_dual_impl("xla")
    for rid in rids:
        r = eng.request(rid)
        require(r.state == FINISHED, (rid, r.state, r.error))
        require(len(res[rid]) == NEW_TOKENS, (rid, len(res[rid])))
    counters = dict(eng.counters)
    for x in jax.tree.leaves(eng.caches):
        x.delete()
    del eng
    gaps = reference_gaps(params, ms, mesh, prompts, [res[r] for r in rids],
                          seed)
    for x in jax.tree.leaves(params):
        x.delete()
    del params
    gc.collect()
    within = {k: float(np.mean(g <= TOL_SIGMA)) for k, g in gaps.items()}
    summary = {
        "phase": name, "lp_pairs": len(plan.pairs), "tp": tp,
        "effective_depth": ms.effective_depth,
        "tokens_served": int(sum(len(res[r]) for r in rids)),
        "serve_wall_s_smoke": round(serve_s, 3),
        "compile_s": round(log.seconds - c0, 3),
        "decode_kernels": kernels,
        "prefix_hits": counters["prefix_hits"],
        "hit_tokens": counters["hit_tokens"],
        "bucket_groups": counters["bucket_groups"],
        "share_within_tol": within.pop("engine"),
        "argmax_agree": float(np.mean(gaps["engine"] == 0)),
        "max_gap_sigma": float(gaps["engine"].max()),
        "controls_within_tol": within,
    }
    print(f"{name}: " + json.dumps(summary), flush=True)
    require(counters["prefix_hits"], f"{name}: no request hit the prefix")
    bad = gaps["engine"] > TOL_SIGMA
    require(not bad.any(),
            f"{name}: {bad.sum()} of {bad.size} generated positions are more "
            f"than {TOL_SIGMA} sigma below the reference top (worst "
            f"{gaps['engine'].max():.3f} sigma)")
    for control, share in within.items():
        require(share < 1.0,
                f"{name}: the check cannot see a {control} fault")
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tp=4 phase on a 1x4 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch import compile_cache
    from repro.launch.mesh import make_serving_mesh

    t_start = time.perf_counter()
    cache_dir = compile_cache.enable()
    log = CompileLog()
    kind = devices[0].device_kind
    print(f"device: {kind} x{args.chips} (platform tpu); "
          f"compile cache: {cache_dir}", flush=True)
    prompts = make_prompts(args.seed, get_config(ARCH).vocab_size)
    print("prompt lengths: " + ",".join(str(len(p)) for p in prompts))
    if args.chips == 4:
        mesh, _ = make_serving_mesh("1x4")
        phases = [("lp_on_tp4", True, mesh)]
    else:
        phases = [("lp_on", True, None), ("lp_off", False, None)]
    for name, lp, mesh in phases:
        run_phase(name, lp=lp, mesh=mesh, prompts=prompts, seed=args.seed,
                  log=log)
    used = devices[:args.chips]
    peak = max(d.memory_stats()["peak_bytes_in_use"] for d in used)
    print(f"compile_s: {log.seconds:.3f} (persistent cache hits "
          f"{log.hits}, misses {log.misses})")
    print(f"peak_bytes_in_use: {peak}")
    print(f"smoke wall seconds (not a metric): "
          f"{time.perf_counter() - t_start:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": "tpu", "kind": kind, "count": len(used)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
