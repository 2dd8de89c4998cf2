"""Readings that set a cell's limit on ``max_gap_sigma``: for each seed,
one run of the cell (its traffic at its rate, a short window) whose
sampled requests are checked against the float32 reference, and the
control read at the same positions: the reference with both inputs of
every matmul rounded to float8 e4m3 (``reference.fp8``), the precision a
later change might serve in. One process, seeds in turn, so set-up is paid once per
seed and compiles once.

    python bench/control.py --workload yi6b.chat --seconds 10 \
        --seeds 201,202,203

Prints one JSON line per seed: the program's widest gap and the
control's, and whether each passes the comparison that decides
``correct`` (``run.judge``); the control's has to come out false. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    R.prepare_environment()
    import jax
    import spec
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 3
    res = spec.resolve(args.workload, spec.benchmark())
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = R.execute(res, seed, args.seconds, False, devices,
                        control=True)
        print(json.dumps({
            "seed": seed, "program": out["checks"]["max_gap_sigma"]["value"],
            "control": out["control_max_gap_sigma"],
            "correct": out["correct"],
            "control_correct": out["control_correct"],
            "checked_tokens": out["info"]["checked_tokens"],
            "attempted": out["attempted"], "failed": out["failed"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
