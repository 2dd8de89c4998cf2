"""The ``prefill_kv_tile_share`` reader: live over total kv tiles of the
window's prefill launches, from counter snapshots or, where the harness
does not snapshot those counters, from the engine's per-step gauges."""
from types import SimpleNamespace

import pytest

import _tiny  # noqa: F401  (puts bench/ on the path)


def _reader():
    import spec
    return spec.load_metric("prefill_kv_tile_share")


def test_reads_the_counter_deltas():
    run = SimpleNamespace(c0={"kv_tiles_live": 100, "kv_tiles_total": 400},
                          c1={"kv_tiles_live": 130, "kv_tiles_total": 520})
    assert _reader().read(run) == pytest.approx(25.0)    # 30 / 120


def test_reads_nothing_without_the_counters():
    keys = {"prefill_tokens": 0, "decoded": 0}
    run = SimpleNamespace(c0=dict(keys), c1=dict(keys), steps=[],
                          window_steps=[])
    # No engine beside ``run`` in the caller's frame: nothing to read.
    assert _reader().read(run) is None
    # An engine whose program has no such counters: nothing either.
    eng = SimpleNamespace(step_count=3, telemetry=SimpleNamespace(
        gauge_series={"queue_depth": [(0, 1)]}))
    assert _reader().read(run) is None
    del eng


def _gauged_run(prefilled, window, gauges, extra_steps=0):
    steps = [SimpleNamespace(prefilled=p) for p in prefilled]
    run = SimpleNamespace(c0={"decoded": 0}, c1={"decoded": 0}, steps=steps,
                          window_steps=[steps[i] for i in window])
    eng = SimpleNamespace(
        step_count=extra_steps + len(steps),
        telemetry=SimpleNamespace(gauge_series={
            "kv_tiles_live_step": [(extra_steps + i, a)
                                   for i, (a, _) in gauges.items()],
            "kv_tiles_total_step": [(extra_steps + i, b)
                                    for i, (_, b) in gauges.items()]}))
    return run, eng


def test_reads_the_window_steps_from_the_engine_gauges():
    # Seven harness steps after five warm-up steps; steps 0, 2, 3 and 6
    # prefilled; the window holds steps 2-5. Only step 2's and 3's
    # launches count: (8 + 4) / (32 + 16).
    run, eng = _gauged_run([7, 0, 9, 3, 0, 0, 4], range(2, 6),
                           {0: (1, 2), 2: (8, 32), 3: (4, 16), 6: (5, 5)},
                           extra_steps=5)
    assert _reader().read(run) == pytest.approx(25.0)
    del eng


def test_reads_nothing_when_the_steps_do_not_line_up():
    # A launch gauged on a step the harness saw prefill nothing.
    run, eng = _gauged_run([7, 0, 9], range(3), {1: (1, 2)})
    assert _reader().read(run) is None
    del eng


def test_traced_tiny_run_reads_the_share(tmp_path, monkeypatch):
    import json
    import os

    import run
    monkeypatch.setattr(run, "TRACE_S", 1.0)
    write = _tiny.write

    def with_share(root):
        here = write(root)
        path = os.path.join(root, "BENCHMARK.json")
        with open(path) as f:
            bench = json.load(f)
        bench["per_layer"].append(
            {"name": "prefill_kv_tile_share", "unit": "%",
             "moves": "ttft_p90_ms", "workloads": ["tiny.chat"]})
        with open(path, "w") as f:
            json.dump(bench, f)
        return here

    monkeypatch.setattr(_tiny, "write", with_share)
    out = _tiny.execute(str(tmp_path), "tiny.chat", trace=True)
    assert out["correct"], out
    # Prefix-on engine: every bucket launch runs the ctx-aware program,
    # whose cold rows see none of the ctx tiles.
    assert 0 < out["metrics"]["prefill_kv_tile_share"]["value"] < 100
