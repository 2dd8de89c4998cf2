"""Benchmark aggregator — one experiment per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]

Order: the shared bench model trains once (cached), then each experiment
reads it. Emits a CSV summary line per experiment plus JSON artifacts under
benchmarks/results/.
"""
from __future__ import annotations

import argparse
import os
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="longer training / denser grids (hours on 1 CPU)")
    ap.add_argument("--fast", action="store_true")  # alias of the default
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    train_steps = 1200 if args.full else 150
    ft_steps = 300 if args.full else 40

    # One process holds the devices for every experiment; on the CPU the
    # LP-speed mesh comes from eight forced host devices (the flag only
    # affects the host platform, so it is harmless on an accelerator).
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    import jax

    from benchmarks import (effective_depth, finetune_recovery, icl_depth,
                            lp_ppl_sweep, lp_speed)
    n_dev = len(jax.devices())
    speed_mesh = "2x4" if n_dev >= 8 else f"1x{n_dev}"
    experiments = {
        # paper Fig. 3/4
        "effective_depth": lambda: effective_depth.run(
            stride=2 if args.full else 8, train_steps=train_steps),
        # paper Fig. 6
        "lp_ppl_sweep": lambda: lp_ppl_sweep.run(train_steps=train_steps),
        # paper Table 1
        "icl_depth": lambda: icl_depth.run(train_steps=train_steps),
        # paper Table 2
        "finetune_recovery": lambda: finetune_recovery.run(
            train_steps=train_steps, ft_steps=ft_steps),
        # paper Fig. 7/8 + Table 3 / Appendix C
        "lp_speed": lambda: lp_speed.run(mesh=speed_mesh, in_process=True),
    }
    print("name,seconds,status")
    rows = []
    for name, fn in experiments.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            fn()
            status = "ok"
        except Exception:
            traceback.print_exc()
            status = "FAILED"
        dt = time.time() - t0
        rows.append((name, dt, status))
        print(f"{name},{dt:.1f},{status}", flush=True)
    print("\nSUMMARY")
    for name, dt, status in rows:
        print(f"  {name:24s} {dt:8.1f}s  {status}")
    if any(s == "FAILED" for _, _, s in rows):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
