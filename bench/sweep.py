"""Find a cell's knee once, on the chip: the highest arrival rate whose
backlog does not grow across a window. One process builds the engine,
warms it up, then offers each rate for a window and drains in between.

    python bench/sweep.py --workload yi6b.chat --rates 0.5,1,1.5,2 \
        --seconds 30 --seed 11

For each rate it prints one JSON line: requests due and admitted in the
window, the queue at the window's start and end, the 50th and 90th
percentile of time to first token of the requests due in the window
(those still waiting at its end count with the wait so far) and the
output tokens per second. The knee goes into the cell's file as a number;
the benchmark itself never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args(argv)
    # The same seed draws the same system prompts in every Traffic, so the
    # prompts preloaded below are the ones each rate's sessions use.
    R.prepare_environment()
    import jax
    import drive as DR
    import model as MB
    import readers as RD
    import spec
    import traffic as TR
    from repro.launch import compile_cache
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    res = spec.resolve(args.workload, spec.benchmark())
    c, p = res["config"], dict(res["traffic"])
    m = c["model"]
    eng, _ = MB.build(c, args.seed)
    base = TR.Traffic(dict(p, rate_per_s=1.0), args.seed, m["vocab_size"],
                      window_s=args.seconds, n_slots=c["serve"]["n_slots"])
    R.warm_up(eng, base, args.seed)
    R.preload_sessions(eng, base)
    for rate in [float(x) for x in args.rates.split(",")]:
        q = dict(p, rate_per_s=rate, preroll_s=5.0, drain_s=0.0)
        tr = TR.Traffic(q, args.seed, m["vocab_size"], window_s=args.seconds,
                        n_slots=c["serve"]["n_slots"])
        d = DR.Driver(eng, tr, m, preroll=5.0, seconds=args.seconds,
                      drain=0.0)
        origin = time.perf_counter()
        clock = lambda: time.perf_counter() - origin  # noqa: E731
        d.run(clock)
        end = clock()
        wr = d.window_recs()
        run = type("Run", (), {})()
        run.window_recs, run.end = wr, end
        t = RD.ttfts(run)
        out = {"rate": rate, "due": len(wr),
               "admitted": sum(r.admit is not None for r in wr),
               "queue_end": eng.sched.n_queued,
               "ttft_p50_ms": 1e3 * RD.nearest_rank(t, 0.5),
               "ttft_p90_ms": 1e3 * RD.nearest_rank(t, 0.9),
               "tok_per_s": sum(n for r in d.recs.values()
                                for tt, n in r.emits
                                if d.start < tt <= d.window_end)
               / (d.window_end - d.start)}
        print(json.dumps(out), flush=True)
        eng.drain()
    return 0


if __name__ == "__main__":
    sys.exit(main())
