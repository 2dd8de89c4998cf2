"""Find everything a run needs by name, from files alone.

- ``BENCHMARK.json`` (the repository root) names the cells, metrics and
  configurations;
- ``bench/configs/<config>.json``: a configuration (model, serving
  geometry, source and how it was cut);
- ``bench/traffic/<traffic>.json``: a traffic mix's parameters, read by
  the one generator in ``traffic.py``;
- ``bench/cells/<cell>.json`` (optional): parameters of one cell laid over
  its traffic mix's, such as the arrival rate found for that model;
- ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``
  returning a number or None;
- ``bench/peaks.json``: the chip's published peaks by ``device_kind``.

A new configuration, traffic mix, cell or metric is a new file here and a
new entry in ``BENCHMARK.json``; no code changes.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(*parts):
    path = os.path.join(*parts)
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(root, "BENCHMARK.json")


def load_config(name: str, here: str = HERE) -> dict:
    return _json(here, "configs", f"{name}.json")


def load_traffic(name: str, here: str = HERE) -> dict:
    return _json(here, "traffic", f"{name}.json")


def cell_params(name: str, here: str = HERE) -> dict:
    path = os.path.join(here, "cells", f"{name}.json")
    return _json(path) if os.path.exists(path) else {}


def load_metric(name: str, here: str = HERE):
    """The reader module of metric ``name`` (its file may hold dots)."""
    path = os.path.join(here, "metrics", f"{name}.py")
    sp = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def peaks(kind: str, here: str = HERE) -> dict:
    """Published peaks of one chip; an unknown ``device_kind`` is an
    error, never a default."""
    table = _json(here, "peaks.json")["chips"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


def resolve(cell: str, bench: dict, here: str = HERE) -> dict:
    """Everything one cell needs: its entry, configuration, traffic
    parameters (the cell's own laid over the mix's) and the metrics it
    reports, split into end-to-end and per-layer."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell not in cells:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[cell]

    def applies(m):
        return cell in m["workloads"] if "workloads" in m else None

    e2e = [m for m in bench["end_to_end"] if applies(m) is not False]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (applies(m) if "workloads" in m else m["moves"] in names)]
    traffic = dict(load_traffic(w["traffic"], here))
    traffic.update(cell_params(cell, here))
    return {"cell": w, "config": load_config(w["config"], here),
            "traffic": traffic, "end_to_end": e2e, "per_layer": layer}
