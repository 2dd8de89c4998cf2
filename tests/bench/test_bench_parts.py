"""Layer parts (``bench/parts/``): a configuration names its layers'
mixer and FFN, and the weights, the reference, the FLOP counts and the
check against the program all follow from the parts' files. Proved with
the program's Mamba-1 mixer on the CPU, end to end through the harness
and beside attention in one stack."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

import _tiny  # noqa: E402

import flops as FL  # noqa: E402
import model as MB  # noqa: E402
import spec  # noqa: E402
import weights as W  # noqa: E402


def test_tiny_mamba_cell_agrees_and_its_control_fails(tmp_path):
    out = _tiny.execute(str(tmp_path), "tinymamba.chat", seed=13,
                        control=True)
    gap = out["checks"]["max_gap_sigma"]
    assert out["correct"], (out["failed"], out["attempted"], out["info"])
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["info"]["checked_tokens"] > 100
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p99_ms", "setup_s"}
    assert out["control_correct"] is False
    assert out["control_max_gap_sigma"] > gap["limit"]
    assert list(out)[-1] == "checks"


def _mamba_config(**model):
    return {"name": "tinymamba", "program_arch": "falcon-mamba-7b",
            "program_overrides": {k: _tiny.MAMBA[k] for k in (
                "d_model", "n_layers", "vocab_size", "ssm_state",
                "dt_rank")},
            "model": dict(_tiny.MAMBA, **model)}


#: Every model key a part reads, for configurations that mix them.
ALL_KEYS = dict(_tiny.MAMBA, **{k: _tiny.MODEL[k] for k in (
    "n_heads", "n_kv_heads", "head_dim", "rope_theta", "d_ff")})


def test_mamba_weights_reach_the_program_as_drawn():
    from repro.model import transformer as T
    c = _mamba_config()
    ms = MB.structure(c)
    dt = jnp.float32
    p = W.program_params(T.abstract_params(ms, dt), MB.segments_of(ms),
                         c["model"], 9, dt)
    want = W.layer_weights(W.seed_key(9), 2, c["model"], dt)
    # Layer 2 is the second half of the LP pair (1, 2): segment 1, half 1.
    got = p["segments"][1]
    for name in ("w_in", "conv_b", "dt_bias", "A_log", "D", "w_out"):
        assert np.array_equal(np.asarray(got["mamba"][name][1]),
                              np.asarray(want[name])), name
    assert np.array_equal(np.asarray(got["ln1"]["scale"][1]),
                          np.asarray(want["ln1"]))
    # The stated draws: dt_bias about -4.6, A_log about 1.5.
    assert abs(float(want["dt_bias"].mean()) + 4.6) < 0.2
    assert abs(float(want["A_log"].mean()) - 1.5) < 0.1


def test_a_program_leaf_no_part_names_raises():
    from repro.model import transformer as T
    c = {"name": "tiny", "program_arch": "yi-6b",
         "program_overrides": {k: _tiny.MODEL[k] for k in (
             "d_model", "n_layers", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab_size", "rope_theta")},
         "model": _tiny.MODEL}
    ms = MB.structure(c)
    abstract = T.abstract_params(ms, jnp.bfloat16)
    abstract["segments"][0]["mlp"]["router"] = jax.ShapeDtypeStruct(
        (64, 4), jnp.bfloat16)
    with pytest.raises(ValueError, match="named by no part"):
        W.program_params(abstract, MB.segments_of(ms), c["model"], 9,
                         jnp.bfloat16)


@pytest.mark.parametrize("program,pattern", [
    ("falcon-mamba-7b", None),                       # dense by default
    ("falcon-mamba-7b", [{"mixer": "mamba", "ffn": "mlp"}]),
    ("falcon-mamba-7b", [{"mixer": "mamba", "ffn": None},
                         {"mixer": "attn", "ffn": "mlp"}]),
    ("yi-6b", [{"mixer": "mamba", "ffn": None}]),
])
def test_a_pattern_that_is_not_the_programs_raises(program, pattern):
    c = _mamba_config(**ALL_KEYS)
    c["program_arch"] = program
    c["model"]["pattern"] = pattern
    if pattern is None:
        del c["model"]["pattern"]
    with pytest.raises(ValueError, match="layer 1"):
        MB.arch_config(c)


def test_an_unknown_part_names_its_file():
    c = _mamba_config(pattern=[{"mixer": "mamba2", "ffn": None}])
    with pytest.raises(FileNotFoundError, match="parts/mamba2.py"):
        MB.arch_config(c)


def test_leaf_draw_ids_are_append_only():
    """The first block's and the top-level leaves keep ids 0-11; every
    other leaf of every part has an id of its own, after them."""
    ids = W.leaf_ids()
    assert {n: ids[n] for n in W.LAYER_LEAVES + W.TOP_LEAVES} == {
        "ln1": 0, "wq": 1, "wk": 2, "wv": 3, "wo": 4, "ln2": 5,
        "w_gate": 6, "w_up": 7, "w_down": 8, "tok": 9, "head": 10,
        "final_norm": 11}
    assert len(set(ids.values())) == len(ids)
    for part in spec.all_parts():
        assert set(part.shapes(ALL_KEYS)) <= set(ids)
        assert all(i >= 12 for i in getattr(part, "IDS", {}).values())


def test_mamba_hand_counts():
    m = _tiny.MAMBA                 # D 64, E 128, N 8, K 4, R 8; float32
    mm = 64 * 256 + 128 * (8 + 16) + 8 * 128 + 128 * 64
    other = 128 * (2 * 4 + 6 * 8 + 4)
    assert FL.layer_matmul_params(m) == mm
    assert FL.attn_flops_per_key(m) == 0 and FL.kv_bytes_per_token(m) == 0
    assert FL.decode_flops(m, 99) == (4 * (2 * mm + other)
                                      + 2 * 64 * 256)
    assert FL.prefill_flops(m, 0, 5) == (5 * 4 * (2 * mm + other)
                                         + 2 * 64 * 256)
    layer = mm + 4 * 128 + 3 * 128 + 128 * 8 + 64
    assert FL.weight_bytes(m) == 4 * (2 * 256 * 64 + 64 + 4 * layer)
    assert FL.decode_bytes(m, 1000) == FL.weight_bytes(m) - 4 * 256 * 64
    # Per slot and layer: 3 inputs of the convolution and the scan state.
    assert FL.state_bytes_per_slot(m) == 4 * (3 * 128 * 4 + 128 * 8 * 4)


def test_a_mixed_pattern_sums_its_parts():
    """Three Mamba + MLP layers to one attention + MLP layer, 8 layers:
    each term counts six Mamba layers and two attention layers."""
    hybrid = dict(ALL_KEYS, n_layers=8, dtype="bfloat16",
                  pattern=[{"mixer": "mamba", "ffn": "mlp"}] * 3
                  + [{"mixer": "attn", "ffn": "mlp"}])
    dense = dict(_tiny.MODEL, n_layers=2)
    mamba = dict(_tiny.MAMBA, n_layers=6, dtype="bfloat16")
    mlp6 = 6 * 3 * 64 * 128
    assert FL.attn_flops_per_key(hybrid) == FL.attn_flops_per_key(dense)
    assert FL.kv_bytes_per_token(hybrid) == FL.kv_bytes_per_token(dense)
    assert FL.state_bytes_per_slot(hybrid) == FL.state_bytes_per_slot(mamba)
    assert FL.decode_flops(hybrid, 9) == (
        FL.decode_flops(dense, 9) + FL.decode_flops(mamba, 9)
        - FL.head_flops(dense) + 2 * mlp6)
    assert FL.layer_matmul_params(hybrid, 3) == \
        FL.layer_matmul_params(dense)


def test_mixed_attention_and_mamba_reference_matches_the_program():
    """The program builds a stack of Mamba and attention layers, each with
    an MLP (Jamba's kinds): its float32 forward agrees with the reference
    composed from the parts, and the fp8 control does not."""
    import reference as RF
    from repro.configs.base import LayerSpec
    from repro.core.lp import LPPlan
    from repro.model import transformer as T
    from repro.parallel.context import ParallelContext
    m = dict(ALL_KEYS, lp_pairs=[[0, 1]],
             pattern=[{"mixer": "mamba", "ffn": "mlp"}] * 3
             + [{"mixer": "attn", "ffn": "mlp"}])
    over = {k: m[k] for k in ("d_model", "n_layers", "vocab_size",
                              "ssm_state", "dt_rank", "n_heads",
                              "n_kv_heads", "head_dim", "rope_theta",
                              "d_ff")}
    over["block_pattern"] = (LayerSpec("mamba", "mlp"),) * 3 + (
        LayerSpec("attn", "mlp"),)
    cfg = MB.arch_config({"name": "hybrid", "program_arch": "yi-6b",
                          "program_overrides": over, "model": m})
    ms = T.build_structure(cfg, plan=LPPlan(((0, 1),)), tp=1)
    params = W.program_params(T.abstract_params(ms, jnp.float32),
                              MB.segments_of(ms), m, 5, jnp.float32)
    toks = np.random.default_rng(5).integers(0, 256, (2, 20))
    with jax.default_matmul_precision("highest"):
        got, _, _ = T.forward_full(params, jnp.asarray(toks), ms=ms,
                                   pc=ParallelContext(), scan_impl="seq")
    pos = np.tile(np.arange(20), (2, 1))
    ref = RF.hidden_logits(m, W.seed_key(5), toks, pos)
    ctl = RF.hidden_logits(m, W.seed_key(5), toks, pos, quant=True)
    sd = float(np.asarray(ref).std())
    err = float(np.abs(np.asarray(got) - np.asarray(ref)).max()) / sd
    ctl_err = float(np.abs(np.asarray(ctl) - np.asarray(ref)).max()) / sd
    assert err < 1e-3 < 0.01 < ctl_err, (err, ctl_err)
