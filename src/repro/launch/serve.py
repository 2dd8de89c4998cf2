"""Serving driver: batched prefill + decode with an LP model.

One-shot fixed batch (the paper's measurement setup):

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --eff-depth 20 --batch 4 --prompt-len 64 --new-tokens 32

Continuous batching over the paged pair-KV cache pool (deployment shape —
requests arrive staggered, share pages, finish independently):

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --eff-depth 20 --continuous --requests 16 --new-tokens 32

Sharded continuous batching (tp > 1: the page pool shards its kv-head axis
over the model axis, scheduling stays host-side):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve --arch tinyllama-1.1b --eff-depth 20 \
        --continuous --mesh 1x2 --requests 16 --new-tokens 32

Without ``--full-config`` this runs the reduced config, which suits the
CPU; with it, the published widths, which need the chip. Weights are
random bf16 from a fixed seed, made already sharded under a mesh. The
persistent compile cache is on (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core.lp import EMPTY_PLAN, plan_for_depth
from repro.launch import compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.model import transformer as T
from repro.parallel.context import ParallelContext
from repro.serve import (AdmissionConfig, DegradeConfig, PagedEngine,
                         PagedServeConfig, QueueFullError, ServeConfig,
                         SpecConfig, TelemetryConfig, generate,
                         make_sharded_generate)


def _parse_buckets(text: str):
    """--bucket-sizes value -> PagedServeConfig.prefill_buckets: "auto"
    (None, the power-of-two ladder), "off" ((), exact-length prefill), or
    comma-separated widths ("8,16,32")."""
    text = text.strip().lower()
    if text == "auto":
        return None
    if text == "off":
        return ()
    try:
        return tuple(int(t) for t in text.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--bucket-sizes {text!r}: expected 'auto', 'off', or "
            "comma-separated ints like '8,16,32'")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--eff-depth", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over the paged KV cache pool")
    ap.add_argument("--requests", type=int, default=16,
                    help="(--continuous) number of synthetic requests")
    ap.add_argument("--page-size", type=int, default=16,
                    help="(--continuous) tokens per cache page")
    ap.add_argument("--mesh", default="1x1",
                    help="1xM device mesh; M > 1 runs the shard_map "
                         "programs with tp=M — needs XLA_FLAGS="
                         "--xla_force_host_platform_device_count>=M on CPU")
    ap.add_argument("--preempt-after", type=int, default=0,
                    help="(--continuous) blocked-head steps before the "
                         "youngest running request is preempted (0 = off)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="(--continuous) radix prefix sharing over whole "
                         "cache pages, tp=1 and sharded --mesh engines "
                         "alike (--no-prefix-cache disables)")
    ap.add_argument("--deadline-steps", type=int, default=0,
                    help="(--continuous) per-request deadline, engine "
                         "steps after submission; overrun requests EXPIRE "
                         "and release their pages (0 = none)")
    # Argument groups mirror the PagedServeConfig sub-configs one-to-one:
    # each group below builds exactly one grouped kwarg.
    adm = ap.add_argument_group(
        "admission (AdmissionConfig)",
        "what enters the engine per step, and at what padded cost")
    adm.add_argument("--prefill-token-budget", type=int, default=4096,
                     help="(--continuous) max prefill tokens admitted per "
                          "step after the first (prefill/decode "
                          "interleave); bucketed admissions cost their "
                          "PADDED width")
    adm.add_argument("--max-queue", type=int, default=0,
                     help="(--continuous) bound the submit queue; a full "
                          "queue sheds the slackest-deadline request for a "
                          "more urgent newcomer, else rejects (0 = "
                          "unbounded)")
    adm.add_argument("--bucket-sizes", type=_parse_buckets, default="auto",
                     help="(--continuous) prefill bucket ladder: 'auto' "
                          "(power-of-two page multiples up to max_len), "
                          "'off' (exact-length prefill, one compile per "
                          "distinct prompt length), or comma-separated "
                          "widths like '16,32,64'")
    deg = ap.add_argument_group(
        "overload degradation (DegradeConfig)",
        "surge admissions at an aggressive-Δ re-pairing of the weights")
    deg.add_argument("--degrade-delta", action="store_true",
                     help="(--continuous) overload degradation: overflow "
                          "admissions run an aggressive-Δ re-pairing of "
                          "the same weights in a reserved slot cohort")
    deg.add_argument("--degrade-slots", type=int, default=0,
                     help="(--degrade-delta) slots reserved for the "
                          "degraded cohort (default: half the batch)")
    deg.add_argument("--degrade-eff-depth", type=int, default=0,
                     help="(--degrade-delta) effective depth of the "
                          "degraded cohort (0 = maximal pairing)")
    spec = ap.add_argument_group(
        "speculative decoding (SpecConfig)",
        "shallow-Δ drafts verified by the full-depth decode program")
    spec.add_argument("--spec-k", type=int, default=0,
                      help="(--continuous) self-speculative decoding: "
                           "draft this many greedy tokens per step with "
                           "the same weights re-paired at an aggressive "
                           "Δ, verify them in one full-depth launch "
                           "(greedy-only, tp=1; 0 = off)")
    spec.add_argument("--spec-delta", type=int, default=0,
                      help="(--spec-k) drafter effective depth (0 = "
                           "maximal pairing)")
    tel = ap.add_argument_group(
        "telemetry (TelemetryConfig)",
        "observation must never change the served bits")
    tel.add_argument("--trace-out", default="",
                     help="(--continuous) write the run's Chrome/Perfetto "
                          "trace_event JSON here (open in chrome://tracing "
                          "or ui.perfetto.dev)")
    tel.add_argument("--metrics-out", default="",
                     help="(--continuous) write the run's metrics snapshot "
                          "here; a .prom suffix writes Prometheus text "
                          "instead of JSON")
    tel.add_argument("--telemetry", action=argparse.BooleanOptionalAction,
                     default=True,
                     help="(--continuous) retain spans/gauge series for "
                          "traces (--no-telemetry caps memory on long "
                          "soaks; counters and faults stay live)")
    tel.add_argument("--profile-decode", action="store_true",
                     help="(--continuous) bracket each decode launch in a "
                          "jax.profiler StepTraceAnnotation (only useful "
                          "under an active jax profiler session)")
    args = ap.parse_args()
    if isinstance(args.bucket_sizes, str):      # default never went through
        args.bucket_sizes = _parse_buckets(args.bucket_sizes)

    compile_cache.enable()
    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = reduced_config(cfg)
    plan = (plan_for_depth(cfg, args.eff_depth) if args.eff_depth
            else EMPTY_PLAN)
    mesh, mesh_m = make_serving_mesh(args.mesh)
    ms = T.build_structure(cfg, plan=plan, tp=mesh_m)
    params = T.init_params(ms, jax.random.PRNGKey(0), jnp.bfloat16, mesh=mesh)
    pc = ParallelContext()

    if args.continuous:
        ps = args.page_size
        max_len = -(-(args.prompt_len + args.new_tokens + 8) // ps) * ps
        deg_slots = (args.degrade_slots or args.batch // 2
                     if args.degrade_delta else 0)
        psv = PagedServeConfig(
            n_slots=args.batch, page_size=ps,
            n_pages=1 + args.batch * (max_len // ps), max_len=max_len,
            temperature=args.temperature,
            prefix_cache=args.prefix_cache,
            preempt_after=args.preempt_after,
            admission=AdmissionConfig(
                prefill_token_budget=args.prefill_token_budget,
                max_queue=args.max_queue,
                prefill_buckets=args.bucket_sizes),
            degrade=DegradeConfig(
                enabled=args.degrade_delta, slots=deg_slots,
                eff_depth=args.degrade_eff_depth),
            spec=SpecConfig(k=args.spec_k, delta=args.spec_delta),
            telemetry_cfg=TelemetryConfig(
                enabled=args.telemetry,
                profile_decode=args.profile_decode))
        if args.trace_out and not args.telemetry:
            ap.error("--trace-out needs telemetry (drop --no-telemetry)")
        eng = PagedEngine(params, ms, psv, mesh=mesh)
        key = jax.random.PRNGKey(1)
        # A shared head (page-aligned) + per-request tails: realistic
        # system-prompt traffic that exercises the radix cache when on.
        shared_len = min(args.prompt_len // 2 // ps * ps, args.prompt_len)
        shared = np.asarray(jax.random.randint(
            jax.random.fold_in(key, 999), (shared_len,), 0, cfg.vocab_size))
        lens = [max(4, args.prompt_len - shared_len - 8 * (i % 3))
                for i in range(args.requests)]
        t0 = time.time()
        rejected = 0
        for i, L in enumerate(lens):
            tail = np.asarray(jax.random.randint(
                jax.random.fold_in(key, i), (L,), 0, cfg.vocab_size))
            prompt = np.concatenate([shared, tail])
            dl = (eng.step_count + args.deadline_steps
                  if args.deadline_steps else None)
            try:
                eng.add_request(prompt, args.new_tokens, deadline=dl)
            except QueueFullError:
                # Bounded queue, nothing slacker to shed: serve a step to
                # make room, then drop this arrival (typed, counted).
                rejected += 1
                eng.step()
        res = eng.drain()
        run = time.time() - t0
        toks = sum(len(v) for v in res.values())
        c = eng.counters
        print(f"arch={cfg.name} eff_depth={ms.effective_depth}/{cfg.n_layers} "
              f"tp={ms.tp} "
              f"continuous: {args.requests} reqs x {args.new_tokens} new, "
              f"slots={psv.n_slots} pages={psv.n_pages - 1}x{ps} "
              f"prefix_cache={'on' if eng.prefix is not None else 'off'} "
              f"preempt_after={args.preempt_after}")
        print(f"run={run:.3f}s throughput={toks / run:.1f} tok/s "
              f"steps={eng.step_count} "
              f"pages alloc/freed={eng.pool.allocated_total}"
              f"/{eng.pool.freed_total} "
              f"prefill_toks={c['prefill_tokens']} "
              f"hit_toks={c['hit_tokens']} "
              f"preemptions={eng.sched.preemptions_total}")
        if eng.spec_k:
            v = c["verify_steps"]
            probed = c["spec_accepted"] + c["spec_rejected"]
            print(f"speculative: k={eng.spec_k} "
                  f"draft_depth={eng.ms_draft.effective_depth} "
                  f"verifies={v} drafts={c['draft_steps']} "
                  f"accept_rate="
                  f"{c['spec_accepted'] / max(probed, 1):.2f} "
                  f"rewound={c['spec_rewound']}")
        if (c["failed"] or c["expired"] or c["shed"] or rejected
                or c["degraded_admissions"]):
            print(f"lifecycle: failed={c['failed']} expired={c['expired']} "
                  f"shed={c['shed']} rejected={rejected} "
                  f"degraded={c['degraded_admissions']}")
        if args.trace_out:
            print("trace:", eng.dump_trace(args.trace_out))
        if args.metrics_out:
            if args.metrics_out.endswith(".prom"):
                with open(args.metrics_out, "w") as f:
                    f.write(eng.metrics_text())
            else:
                import json
                with open(args.metrics_out, "w") as f:
                    json.dump(eng.metrics_snapshot(), f, indent=1,
                              sort_keys=True)
            print("metrics:", args.metrics_out)
        print("sample:", res[0][:16].tolist())
        return
    sv = ServeConfig(max_len=args.prompt_len + args.new_tokens + 8,
                     temperature=args.temperature)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    if mesh is not None:
        assert args.temperature == 0.0, "--mesh one-shot is greedy-only"
        # Build the loop ONCE so the warm call compiles the programs the
        # timed call reuses.
        gen = make_sharded_generate(ms, mesh, sv, batch=args.batch,
                                    prompt_len=args.prompt_len)
        out = gen(params, prompts, args.new_tokens)    # warm + compile
        t0 = time.time()
        out = gen(params, prompts, args.new_tokens)
        run = time.time() - t0
        tput = args.batch * args.new_tokens / run
        print(f"arch={cfg.name} eff_depth={ms.effective_depth}/"
              f"{cfg.n_layers} tp={ms.tp} batch={args.batch} "
              f"new={args.new_tokens}")
        print(f"run={run:.3f}s throughput={tput:.1f} tok/s")
        print("sample:", out[0, :16].tolist())
        return
    extras = {}
    if cfg.prefix_len:
        extras["prefix"] = jnp.zeros((args.batch, cfg.prefix_len, cfg.d_model))
    if cfg.enc_layers:
        extras["frames"] = jnp.zeros((args.batch, cfg.enc_seq, cfg.d_model))

    gen = jax.jit(lambda p, x: generate(
        p, x, args.new_tokens, ms=ms, pc=pc, sv=sv,
        prefix=extras.get("prefix"), frames=extras.get("frames")))
    t0 = time.time()
    out = jax.block_until_ready(gen(params, prompts))
    compile_time = time.time() - t0
    t0 = time.time()
    out = jax.block_until_ready(gen(params, prompts))
    run = time.time() - t0
    tput = args.batch * args.new_tokens / run
    print(f"arch={cfg.name} eff_depth={ms.effective_depth}/{cfg.n_layers} "
          f"batch={args.batch} new={args.new_tokens}")
    print(f"compile={compile_time:.2f}s run={run:.3f}s throughput={tput:.1f} tok/s")
    print("sample:", out[0, :16].tolist())


if __name__ == "__main__":
    main()
