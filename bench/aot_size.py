"""Size a configuration's slots and page pool for one TPU v5e chip without
the chip: compile the engine's decode program and every prefill rung the
traffic can reach for a *described* v5e and read each program's
``memory_analysis()``.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/aot_size.py yi6b \
        [--slots 8] [--pages 1537]

A compile here is a rehearsal, not a chip run: nothing executes, and the
bytes are the compiler's plan for one program at a time. The chip must
hold the weights, the page pool and the largest program's temporaries at
once, so the script prints their sum against the chip's memory.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--pages", type=int, default=0)
    ap.add_argument("--rungs", default="",
                    help="comma-separated rungs (default: the whole ladder)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import model as MB
    import spec
    from repro.model import transformer as T
    from repro.parallel.context import ParallelContext
    from repro.serve import bucketing as BK
    from repro.serve import engine as E
    from repro.serve import paged_cache as PG

    jax.config.update("jax_enable_compilation_cache", False)
    c = spec.load_config(args.config)
    if args.slots:
        c["serve"]["n_slots"] = args.slots
    if args.pages:
        c["serve"]["n_pages"] = args.pages
    s = c["serve"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    ms = MB.structure(c)
    psv = MB.serve_config(c)
    dt = jnp.dtype(c["model"]["dtype"])
    pc = ParallelContext()

    def shaped(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    params = shaped(T.abstract_params(ms, dt))
    caches = shaped(PG.paged_cache_meta(
        ms, n_slots=s["n_slots"], n_pages=s["n_pages"],
        page_size=s["page_size"], dtype=dt)[0])
    i32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32, sharding=one)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    n, pps = s["n_slots"], s["max_len"] // s["page_size"]
    report = {"config": c["name"], "n_slots": n, "n_pages": s["n_pages"],
              "weights_bytes": nbytes(params), "pool_bytes": nbytes(caches),
              "programs": {}}

    def record(name, compiled):
        ma = compiled.memory_analysis()
        report["programs"][name] = {
            "temp_bytes": int(ma.temp_size_in_bytes),
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes)}
        print(name, json.dumps(report["programs"][name]), flush=True)

    dec = jax.jit(E.make_paged_decode_fn(ms, pc, psv), donate_argnums=(1,))
    record("decode", dec.lower(
        params, caches, i32(n), i32(n), i32(n, pps),
        jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one), key).compile())
    rungs = ([int(r) for r in args.rungs.split(",")] if args.rungs
             else BK.default_buckets(s["max_len"], s["page_size"]))
    ctx = pps - 1 if s["prefix_cache"] else 0   # ctx-aware programs
    for b in rungs:
        rows = BK.rows_for_bucket(b, n, s["prefill_token_budget"])
        fn = jax.jit(E.make_paged_bucket_prefill_fn(ms, pc, psv, b, rows,
                                                    ctx),
                     donate_argnums=(1,))
        extra = [i32(rows, ctx), i32(rows)] if ctx else []
        record(f"prefill_{b}x{rows}", fn.lower(
            params, caches, i32(rows, b), i32(rows),
            i32(rows, b // s["page_size"]), *extra, key).compile())
    worst = max(p["temp_bytes"] for p in report["programs"].values())
    report["worst_temp_bytes"] = worst
    report["total_bytes"] = (report["weights_bytes"] + report["pool_bytes"]
                             + worst)
    report["hbm_bytes"] = spec.peaks("TPU v5 lite")["hbm_bytes"]
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
