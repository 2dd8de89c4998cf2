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
- ``bench/parts/<part>.py``: one module per part of a layer, a mixer
  (``attn``, ``mamba``) or an FFN (``mlp``): its leaves, the program's
  layer kind it computes, its float32 forward for the reference and its
  FLOP and byte counts (``parts/attn.py`` says what a part module holds).
  A configuration names each layer's parts in its model's optional
  ``pattern``, a list of ``{"mixer": <part>, "ffn": <part> | null}``
  repeated to ``n_layers`` as the program repeats its block pattern;
  without it every layer is ``attn`` and ``mlp``;
- ``bench/peaks.json``: the chip's published peaks by ``device_kind``.

A new configuration, traffic mix, cell, metric or layer part is a new
file here and, but for a part, a new entry in ``BENCHMARK.json``; no code
changes.
"""
from __future__ import annotations

import functools
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


#: A model's layers when it names no ``pattern``: the dense decoder layer.
DENSE = ({"mixer": "attn", "ffn": "mlp"},)


def pattern(m: dict) -> tuple:
    """The model dict's layer pattern as ``(mixer, ffn)`` part names (the
    FFN None where the layer has none), one entry per pattern position."""
    return tuple((e["mixer"], e["ffn"]) for e in m.get("pattern", DENSE))


def layer_kind(m: dict, layer: int) -> tuple:
    """``(mixer, ffn)`` part names of layer ``layer``."""
    pat = pattern(m)
    return pat[layer % len(pat)]


@functools.lru_cache(maxsize=None)
def load_part(name: str, here: str = HERE):
    """The module of layer part ``name``; a name with no file is an error
    that names the file it looked for."""
    path = os.path.join(here, "parts", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no layer part {name!r}: {path} is missing")
    sp = importlib.util.spec_from_file_location(
        "bench_part_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def parts_of(kind: tuple, here: str = HERE) -> list:
    """The part modules of one layer kind ``(mixer, ffn)``: the mixer,
    then the FFN where there is one."""
    return [load_part(n, here) for n in kind if n is not None]


def all_parts(here: str = HERE) -> list:
    """Every part module under ``parts/``, by file name."""
    d = os.path.join(here, "parts")
    return [load_part(f[:-3], here) for f in sorted(os.listdir(d))
            if f.endswith(".py")]


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
