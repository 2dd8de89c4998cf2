"""Benchmark one cell of ``BENCHMARK.json`` on the chip this process finds.

    python bench/run.py --workload yi6b.chat --seed 7 --seconds 30 --trace 0

Steps: refuse to run unless JAX's first device is a TPU and there are as
many as the cell asks for; build the cell's configuration with weights
made on the device from ``--seed`` and the serving engine as the program's
own entry builds it (``model.py``); warm up the prefill rungs the cell's
traffic reaches (set-up); run the traffic's pre-roll (set-up); measure
for ``--seconds``; let the requests due in the window finish; read the
peak device memory; free the engine; check a seeded sample of the served
requests against the float32 reference (``reference.py``); print one
JSON line.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1`` runs
the same window, then profiles the next few seconds of the same traffic
(starting the profiler stalls the host, so it stays out of the window),
and reports the cell's per-layer metrics with the device's busy and
window seconds and a breakdown of the trace.

The result's last key, ``checks``, holds each number compared and its
limit; the same pairs are the last lines on standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

#: JAX's persistent compilation cache, inside the checkout at a fixed path
#: (the path is part of nothing that moves between runs of a checkout).
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: Seconds the profiler records in a ``--trace 1`` run, right after the
#: window closes, with the traffic still running: long enough that
#: several prefill launches fall inside (eight arrivals in ``yi6b.chat``).
TRACE_S = 10.0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--base-seed", type=int, default=None,
                    help="draw the traffic's schedule from this seed instead "
                    "of the mix's own base_seed, to check a gain on a "
                    "schedule it was not written against")
    return ap.parse_args(argv)


def prepare_environment() -> None:
    """Before JAX is imported: the checkout's compile cache, and no
    compiler logs outside the checkout."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


class CompileCount:
    """Times of XLA backend compiles (persistent-cache loads included)."""

    def __init__(self):
        import jax.monitoring as M
        self.times = []

        def on(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.times.append(time.perf_counter())

        M.register_event_duration_secs_listener(on)

    def between(self, a: float, b: float) -> int:
        return sum(a <= t <= b for t in self.times)


def warm_up(eng, traffic, seed: int) -> list:
    """Compile (or load) the decode program and every prefill rung the
    traffic can reach, by serving one two-token request per rung."""
    from repro.serve import bucketing as BK
    ladder = eng._buckets or ()
    lo = BK.bucket_for(traffic.shortest_prefill(), ladder)
    hi = BK.bucket_for(min(traffic.longest_prompt(), eng.psv.max_len - 2),
                       ladder)
    rungs = [b for b in ladder if lo <= b <= hi]
    rng = np.random.default_rng([seed, 1])
    for b in rungs:
        n = max(1, b - 2)
        eng.add_request(rng.integers(0, eng.ms.cfg.vocab_size, n), 2)
    eng.drain()
    return rungs


def preload_sessions(eng, traffic) -> None:
    """A deployment's cache holds its system prompts: prefill each once."""
    if traffic.kind != "sessions":
        return
    for sp in traffic.system:
        eng.add_request(sp, 1)
    eng.drain()


def sample_checked(driver, traffic_p: dict, seed: int, backlog: bool):
    """The requests the reference checks: the longest finished one and
    others drawn from the seed, from those due in the window (in a
    backlog, those that finished after the window opened)."""
    if backlog:
        pool = [r for r in driver.recs.values()
                if r.ok and r.done is not None and r.done > driver.start]
    else:
        pool = [r for r in driver.window_recs() if r.ok]
    if not pool:
        return []
    k = min(int(traffic_p["check_requests"]), len(pool))
    longest = max(pool, key=lambda r: len(r.item.prompt) + len(r.out))
    rest = [r for r in pool if r is not longest]
    rng = np.random.default_rng([seed, 2])
    pick = [rest[i] for i in rng.choice(len(rest), k - 1, replace=False)] \
        if k > 1 else []
    return [longest] + pick


def _quantiles_ms(xs):
    """p50, p90, p95 and p99 (nearest rank) of seconds, in ms."""
    import readers as RD
    return [1e3 * RD.nearest_rank(xs, q) for q in (0.5, 0.9, 0.95, 0.99)] \
        if xs else []


def judge(gap, limit: float) -> bool:
    """The one comparison that decides ``correct``, for the program and
    for the control alike: a widest gap was read, and it is at most the
    cell's limit."""
    return gap is not None and gap <= limit


def free(eng) -> None:
    import jax
    for x in jax.tree.leaves([eng.caches, eng.params]):
        x.delete()


def execute(res: dict, seed: int, seconds: float, trace: bool, devices,
            *, control: bool = False) -> dict:
    """One run of one cell; returns the result dict (the JSON line)."""
    import jax
    import drive as DR
    import devtrace as DT
    import model as MB
    import readers as RD
    import reference as RF
    import spec
    import traffic as TR
    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCount()
    c, p = res["config"], res["traffic"]
    m = c["model"]
    kind = devices[0].device_kind
    # Peaks exist for chips only; a CPU rehearsal reports no utilization.
    peak = spec.peaks(kind) if devices[0].platform == "tpu" else None

    eng, _ = MB.build(c, seed)
    traffic = TR.Traffic(p, seed, m["vocab_size"], window_s=seconds,
                         n_slots=c["serve"]["n_slots"])
    rungs = warm_up(eng, traffic, seed)
    preload_sessions(eng, traffic)
    tdir = tracer = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        tracer = DT.Tracer(tdir, p["preroll_s"] + seconds, TRACE_S)
    driver = DR.Driver(eng, traffic, m, preroll=p["preroll_s"],
                       seconds=seconds, drain=p["drain_s"], tracer=tracer)
    origin = time.perf_counter()
    clock = lambda: time.perf_counter() - origin      # noqa: E731
    driver.run(clock, finished_after=int(p["check_requests"]))
    end = clock()
    used = devices[:res["cell"]["chips"]]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in used)
    reduced = None
    if trace:
        pd = DT.load(tdir)
        reduced = DT.reduce(pd) if devices[0].platform == "tpu" else \
            DT.reduce(pd, **CPU_TRACE)
        shutil.rmtree(tdir, ignore_errors=True)
    run = SimpleNamespace(
        recs=list(driver.recs.values()), window_recs=driver.window_recs(),
        steps=driver.steps, window_steps=driver.window_steps(),
        start=driver.start, window_end=driver.window_end, end=end,
        c0=driver.c0, c1=driver.c1, n_slots=c["serve"]["n_slots"],
        setup_s=origin + driver.start - T_START, m=m, peak=peak,
        trace=reduced)
    metrics = {}
    for md in (res["per_layer"] if trace else res["end_to_end"]):
        v = spec.load_metric(md["name"]).read(run)
        if v is not None:
            metrics[md["name"]] = {"value": float(v), "unit": md["unit"]}
    wr = driver.window_recs()
    checked = sample_checked(driver, p, seed, traffic.kind == "backlog")
    info = {
        "window_requests": len(wr),
        "window_steps": len(run.window_steps),
        "window_tokens": sum(r.n_out for r in wr),
        "lateness_p50_ms": 1e3 * float(np.median(driver.lateness or [0])),
        "lateness_max_ms": 1e3 * float(max(driver.lateness or [0])),
        "ttft_ms_p50_p90_p95_p99": _quantiles_ms(RD.ttfts(run)),
        "gap_ms_p50_p90_p95_p99": _quantiles_ms(RD.gaps(run)),
        "compiles_in_window": compiles.between(origin + driver.start,
                                               origin + driver.window_end),
        "warm_rungs": rungs,
        "traced_steps": sum(s.traced for s in driver.steps),
        "traced_prefill_steps": sum(s.traced and s.prefilled > 0
                                    for s in driver.steps),
        "checked_requests": len(checked),
        "checked_tokens": int(sum(len(r.out) for r in checked)),
    }
    seqs = [(r.item.prompt, r.out) for r in checked]
    max_len = c["serve"]["max_len"]
    del run, driver
    free(eng)
    del eng
    gc.collect()
    t_ref = time.perf_counter()
    gap = ctl = None
    if seqs:
        g, cg = RF.gaps(m, seed, seqs, max_len, control=control)
        gap = float(g.max())
        ctl = float(cg.max()) if control else None
    info["reference_s"] = time.perf_counter() - t_ref
    limit = p["gap_limit_sigma"]
    correct = judge(gap, limit)
    out = {
        "correct": bool(correct),
        "attempted": len(wr),
        "failed": sum(1 for r in wr if not r.ok),
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(used), "memory_peak_bytes": int(mem)},
        "info": info,
    }
    if control:
        out["control_max_gap_sigma"] = ctl
        out["control_correct"] = judge(ctl, limit)
    if reduced is not None:
        out["device"]["busy_s"] = reduced["busy_s"]
        out["device"]["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {"max_gap_sigma": {"value": gap, "limit": limit}}
    return out


#: Stand-in device lines for a trace recorded on the CPU (tests only).
CPU_TRACE = {"plane_prefix": "/host:CPU", "line": "tf_XLAPjRtCpuClient",
             "keep": lambda n: not n.startswith(("ThreadpoolListener",
                                                 "end:"))}


def main(argv=None, *, require_chip: bool = True, bench_root: str = ROOT,
         here: str = HERE) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    prepare_environment()
    import jax
    import spec
    res = spec.resolve(args.workload, spec.benchmark(bench_root), here)
    if args.base_seed is not None:
        res = dict(res, traffic=dict(res["traffic"],
                                     base_seed=args.base_seed))
    devices = jax.devices()
    chips = res["cell"]["chips"]
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < chips):
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    out = execute(res, args.seed, args.seconds, bool(args.trace), devices)
    for name, ck in out["checks"].items():
        print(f"check {name} {ck['value']} limit {ck['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
