"""Trace reduction: interval arithmetic by hand, and the whole reduction on
a small profiler trace recorded on the CPU (its executor threads stand in
for the device)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "bench")))

import devtrace as DT  # noqa: E402


def test_union_covered_gaps():
    m = DT.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert m == [(0, 3), (5, 9)]
    assert DT.covered(m, 0, 10) == 7
    assert DT.covered(m, 2, 6) == 2
    assert DT.gaps(m, 0, 10) == [(3, 5), (9, 10)]
    assert DT.gaps(m, 1, 4) == [(3, 4)]


def test_reduce_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    import run

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    r = DT.reduce(DT.load(str(tmp_path)), **run.CPU_TRACE)
    assert 0 < r["busy_s"] < r["window_s"]
    assert len(r["step_busy_s"]) == 3
    for (s, e), b in zip(r["step_spans_s"], r["step_busy_s"]):
        assert 0 < b <= e - s + 1e-9
    assert sum(r["step_busy_s"]) <= r["busy_s"] + 1e-9
    assert r["device_ops"] and all(d > 0 for _, d in r["device_ops"])
    assert r["idle_gaps"] and all(d > 0 for _, d in r["idle_gaps"])
    with pytest.raises(ValueError):
        DT.reduce(DT.load(str(tmp_path)), plane_prefix="/device:TPU:")
