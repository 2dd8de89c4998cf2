"""The correctness check at a size a test run holds: the program agrees
with the float32 reference on every cell kind, and the control (the
reference in fp8 put in the program's place) fails the limit."""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

import _tiny  # noqa: E402


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.sessions",
                                  "tiny.longdoc"])
def test_program_agrees_with_the_reference(tmp_path, cell):
    out = _tiny.execute(str(tmp_path), cell, control=(cell == "tiny.chat"))
    gap = out["checks"]["max_gap_sigma"]
    assert out["correct"], gap
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["info"]["compiles_in_window"] == 0
    assert list(out)[-1] == "checks"
    want = {"tiny.chat": {"ttft_p90_ms", "itl_p99_ms", "setup_s"},
            "tiny.sessions": {"ttft_p90_ms", "itl_p99_ms", "setup_s"},
            "tiny.longdoc": {"output_tok_per_s", "setup_s"}}[cell]
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    if cell == "tiny.chat":
        # The control (fp8 matmul inputs), put through the same
        # comparison that decides ``correct``, comes out not correct.
        assert out["control_correct"] is False
        assert out["control_max_gap_sigma"] > gap["limit"]


def test_traced_run_reports_the_per_layer_metrics(tmp_path, monkeypatch):
    # The profiler records a stretch after the window; the drain after it
    # takes no new work, and every request due in the window finishes.
    import run
    monkeypatch.setattr(run, "TRACE_S", 1.0)
    out = _tiny.execute(str(tmp_path), "tiny.chat", trace=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert {"queue_wait_p90_ms", "decode_step_ms.tail",
            "device_idle_share.tail"} <= set(out["metrics"])
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["info"]["traced_steps"] > 0
    assert out["breakdown"]["device_ops"]
    assert list(out)[-1] == "checks"


def test_weights_reach_the_program_as_drawn():
    import model as MB
    import weights as W
    from repro.model import transformer as T
    c = {"name": "tiny", "program_arch": "yi-6b",
         "program_overrides": {k: _tiny.MODEL[k] for k in (
             "d_model", "n_layers", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab_size", "rope_theta")},
         "model": _tiny.MODEL}
    ms = MB.structure(c)
    dt = jax.numpy.bfloat16
    p = W.program_params(T.abstract_params(ms, dt), MB.segments_of(ms),
                         c["model"], 9, dt)
    key = W.seed_key(9)
    want = W.layer_weights(key, 2, c["model"], dt)
    # Layer 2 is the second half of the LP pair (1, 2): segment 1, half 1.
    got = p["segments"][1]
    assert np.array_equal(np.asarray(got["attn"]["wq"][1]),
                          np.asarray(want["wq"]))
    assert np.array_equal(np.asarray(got["mlp"]["w_down"][1]),
                          np.asarray(want["w_down"]))
    assert np.array_equal(np.asarray(got["ln2"]["scale"][1]),
                          np.asarray(want["ln2"]))
    tok = W.draw(key, W.TOP, "tok", W.top_shapes(c["model"])["tok"], dt)
    assert np.array_equal(np.asarray(p["embed"]["tok"]), np.asarray(tok))
