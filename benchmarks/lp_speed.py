"""Paper Fig. 7/8 + Table 3 + Appendix C — inference speed and the source
of the acceleration.

Three measurements, the first two on a device mesh (by default eight
forced CPU host devices in a child process; ``run(in_process=True)``
measures on the caller's own devices):
  (a) STRUCTURAL (the dry-run analogue of the paper's flame graphs):
      all-reduce count + wire bytes of one decode step, prefill and train
      micro, vanilla vs LP — LP must remove exactly 2 ARs per pair.
  (b) WALL-CLOCK: decode-step latency on the CPU mesh (collectives are
      real inter-device copies here), vanilla vs LP across Δ.
  (c) LAUNCH COUNTS: per-decode-step attention kernel launches and cache
      ring-slot writes. The fused pair path (stacked caches +
      decode_attention_pair) must show ONE attention launch per paired
      phase — pairs collapse 2 launches and 4 cache writes into 1 and 2.

``--structural`` (or run(structural_only=True)) skips the wall-clock half
so CI can gate on (a) + (c) cheaply.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks import common as C

def measure(structural_only: bool, mesh_spec: str):
    """Rows of (a)-(c) for Δ in {0, 2, 4, 6} on a ``mesh_spec`` (DxM) mesh
    of THIS process's devices: tp = M."""
    import time

    import jax
    import jax.numpy as jnp
    from repro.analysis.roofline import collective_bytes, jaxpr_primitive_count
    from repro.configs import get_config, reduced_config
    from repro.core.lp import LPPlan, plan_range
    from repro.launch.mesh import parse_mesh_spec
    from repro.model import attention as ATT
    from repro.model import stack as STK
    from repro.model import transformer as T
    from repro.parallel.context import ParallelContext
    from repro.serve.engine import ServeConfig, make_sharded_serve_step

    cfg = reduced_config(get_config("tinyllama-1.1b"), n_layers=12)
    DATA, MODEL = parse_mesh_spec(mesh_spec)
    if DATA * MODEL > len(jax.devices()):
        raise ValueError(f"mesh {DATA}x{MODEL} needs {DATA * MODEL} devices, "
                         f"this process has {len(jax.devices())}")
    mesh = jax.make_mesh((DATA, MODEL), ("data", "model"))
    MAXLEN = 512
    BATCH = 8

    def build(plan):
        ms = T.build_structure(cfg, plan=plan, tp=MODEL)
        sv = ServeConfig(max_len=MAXLEN, kv_mode="heads",
                         cache_dtype=jnp.float32)
        fn, c_abs, c_specs, pc = make_sharded_serve_step(ms, mesh, sv,
                                                         batch=BATCH)
        params = T.init_params(ms, jax.random.PRNGKey(0))
        caches = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), c_abs)
        tok = jnp.zeros((BATCH,), jnp.int32)
        key = jax.random.PRNGKey(1)
        return ms, fn, params, caches, tok, key

    def attn_launches(plan):
        # Kernel launches per decode step: trace the SINGLE-DEVICE decode
        # step with the Pallas decode impl and count pallas_call eqns per
        # executed step (scan bodies weighted by trip count). The fused
        # pair path makes this n_layers - n_pairs; the per-half loop would
        # give n_layers.
        ms1 = T.build_structure(cfg, plan=plan, tp=1)
        params = jax.eval_shape(
            lambda: T.init_params(ms1, jax.random.PRNGKey(0)))
        c_abs, _ = T.cache_meta(ms1, batch=1, max_len=64, dtype=jnp.float32)
        ATT.set_decode_impl("pallas")
        try:
            jaxpr = jax.make_jaxpr(
                lambda p, c: T.decode_step(p, jnp.zeros((1,), jnp.int32), c,
                                           jnp.int32(3), ms=ms1,
                                           pc=ParallelContext()))(params,
                                                                  c_abs)
        finally:
            ATT.set_decode_impl("xla")
        return jaxpr_primitive_count(jaxpr, "pallas_call")

    rows = []
    for n_pairs in (0, 2, 4, 6):
        plan = LPPlan(plan_range(cfg, 0, 12).pairs[:n_pairs])
        ms, fn, params, caches, tok, key = build(plan)
        # (a) structural: collective + cache-write counts from compiled HLO
        # (scans unrolled)
        STK.set_scan_unroll(True)
        try:
            low = fn.lower(params, tok, caches, jnp.int32(64), key)
            txt = low.compile().as_text()
        finally:
            STK.set_scan_unroll(False)
        coll = collective_bytes(txt)
        row = {
            "delta": plan.delta,
            "eff_depth": ms.effective_depth,
            "ar_count": int(coll.get("count:all-reduce", 0)),
            "coll_bytes": coll.get("total", 0.0),
            "cache_writes": txt.count("dynamic-update-slice("),
            "attn_launches": attn_launches(plan),
        }
        # (b) wall clock: median of 30 steps after warmup
        if not structural_only:
            nxt, caches = fn(params, tok, caches, jnp.int32(64), key)  # warm
            jax.block_until_ready(nxt)
            times = []
            for i in range(30):
                t0 = time.perf_counter()
                nxt, caches = fn(params, nxt, caches, jnp.int32(65 + i), key)
                jax.block_until_ready(nxt)
                times.append(time.perf_counter() - t0)
            times.sort()
            row["decode_ms"] = round(times[len(times) // 2] * 1e3, 3)
        rows.append(row)
    return rows


# CPU rehearsal child: eight forced host devices, never the accelerator
# (a parent that imported JAX may hold it).
_CHILD = """
import json, sys
from benchmarks.lp_speed import measure
print("RESULT " + json.dumps(measure(sys.argv[1] == "1", sys.argv[2])))
"""


def run(structural_only: bool = False, mesh: str = "2x4",
        in_process: bool = False):
    """``in_process``: measure on this process's own devices (a caller that
    already holds them, e.g. benchmarks/run.py); otherwise in a child on
    eight forced CPU host devices. DxM mesh: tp = M (the 2-ARs-per-pair
    claim is tp-degree-invariant; CI gates it at tp=4 and tp=2)."""
    if in_process:
        rows = measure(structural_only, mesh)
    else:
        root = os.path.join(os.path.dirname(__file__), "..")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["PYTHONPATH"] = os.pathsep.join([os.path.join(root, "src"), root])
        r = subprocess.run(
            [sys.executable, "-c", _CHILD, "1" if structural_only else "0",
             mesh], capture_output=True, text=True, env=env, timeout=1200)
        assert r.returncode == 0, r.stdout + r.stderr
        rows = json.loads([l for l in r.stdout.splitlines()
                           if l.startswith("RESULT")][0][7:])
    tp = int(mesh.split("x")[1])
    base = rows[0]
    hdr = (f"{'Δ':>3s} {'depth':>5s} {'ARs':>4s} {'launch':>6s} "
           f"{'writes':>6s} {'collGB':>8s}")
    if not structural_only:
        hdr += f" {'decode ms':>10s} {'speedup':>8s}"
    print(hdr)
    for row in rows:
        line = (f"{row['delta']:3d} {row['eff_depth']:5d} {row['ar_count']:4d} "
                f"{row['attn_launches']:6d} {row['cache_writes']:6d} "
                f"{row['coll_bytes'] / 1e9:8.4f}")
        if not structural_only:
            sp = base["decode_ms"] / row["decode_ms"]
            row["speedup"] = round(sp, 3)
            line += f" {row['decode_ms']:10.3f} {sp:8.3f}x"
        print(line)
    for row in rows[1:]:
        pairs = row["delta"] // 2
        # The paper's structural claim: 2 fewer ARs per pair (tp > 1 only:
        # a one-device mesh has no all-reduce to remove).
        if tp > 1:
            assert base["ar_count"] - row["ar_count"] == 2 * pairs, (base, row)
        # The fused decode claim: ONE attention launch per paired phase.
        # (cache_writes is reported, not gated: the HLO dynamic-update-slice
        # count also includes scan-carry updates, so it has no clean
        # per-pair delta — the scatter-count gate lives in
        # benchmarks/serve_throughput.py --structural, counted in jaxpr.)
        assert base["attn_launches"] - row["attn_launches"] == pairs, (base, row)
    # Distinct file per mesh so the tp=2 sharded-structural run never
    # clobbers the tp=4 baseline artifact (serve_throughput's _tp suffix
    # convention); the payload records the mesh either way.
    name = "lp_speed" if mesh == "2x4" else f"lp_speed_{mesh}"
    C.save_result(name, {"mesh": mesh, "rows": rows})
    return {"mesh": mesh, "rows": rows}


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description="LP decode speed benchmark")
    ap.add_argument("--structural", action="store_true",
                    help="skip wall-clock timing; assert only the AR-count "
                         "and launch-count invariants (CI gate)")
    ap.add_argument("--mesh", default="2x4",
                    help="DxM device mesh (8 host devices in a child); "
                         "tp = M — e.g. 4x2 gates the claims at tp=2")
    args = ap.parse_args()
    run(structural_only=args.structural, mesh=args.mesh)
