"""The benchmark is data: every cell, configuration, traffic mix and metric
is found by name from files alone, BENCHMARK.json keeps the contract's
shape, and the entry point refuses to run without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.join(ROOT, "bench"))

import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
B = spec.benchmark()


def test_every_cell_resolves_from_files():
    for w in B["workloads"]:
        r = spec.resolve(w["name"], B)
        assert r["config"]["name"] == w["config"]
        assert r["traffic"]["kind"] in ("open_loop", "sessions", "backlog")
        names = {m["name"] for m in r["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert r["per_layer"]
        for m in r["end_to_end"] + r["per_layer"]:
            assert callable(spec.load_metric(m["name"]).read)
        if r["traffic"]["kind"] != "backlog":
            assert r["traffic"]["rate_per_s"] > 0
        assert r["traffic"]["gap_limit_sigma"] > 0


def test_benchmark_json_shape():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51
    cells = {w["name"] for w in B["workloads"]}
    cfgs = {c["name"] for c in B["configs"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in B["paths"]))
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    names = []
    for m in B["end_to_end"] + B["per_layer"]:
        names.append(m["name"])
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"]: m for m in B["end_to_end"]}
    for m in B["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads",
                                                              cells))
    assert len(names) == len(set(names))
    assert len(json.dumps(B)) < 64 * 1024


def test_lookup_by_name_from_new_files_alone(tmp_path):
    """A new configuration, traffic mix and cell are new files and new
    entries; the resolver finds them with no code change."""
    here = tmp_path / "bench"
    for d in ("configs", "traffic", "cells"):
        (here / d).mkdir(parents=True)
    (here / "configs" / "newcfg.json").write_text(json.dumps(
        {"name": "newcfg", "model": {}}))
    (here / "traffic" / "newmix.json").write_text(json.dumps(
        {"kind": "open_loop", "rate_per_s": 1.0}))
    (here / "cells" / "newcfg.newmix.json").write_text(json.dumps(
        {"rate_per_s": 2.5}))
    bench = {"workloads": [{"name": "newcfg.newmix", "config": "newcfg",
                            "traffic": "newmix", "chips": 1}],
             "end_to_end": [{"name": "setup_s", "unit": "s"},
                            {"name": "ttft_p90_ms", "unit": "ms"}],
             "per_layer": [{"name": "queue_wait_p90_ms", "unit": "ms",
                            "moves": "ttft_p90_ms"},
                           {"name": "decode_occupancy", "unit": "%",
                            "moves": "output_tok_per_s"}]}
    r = spec.resolve("newcfg.newmix", bench, str(here))
    assert r["config"]["name"] == "newcfg"
    assert r["traffic"]["rate_per_s"] == 2.5          # the cell's own
    assert [m["name"] for m in r["per_layer"]] == ["queue_wait_p90_ms"]
    with pytest.raises(KeyError):
        spec.resolve("nope", bench, str(here))


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi6b.chat",
         "--seed", str(2 ** 33), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in B["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""
