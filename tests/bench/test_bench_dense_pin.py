"""The dense block stays bit for bit what it was before the harness split
its layer into parts (``bench/parts/``): the program's parameters as
handed over, the float32 reference's logits and the fp8 control's logits
for the tiny configuration at seed 9, each pinned by a SHA-256 digest
recorded before the split."""
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

import _tiny  # noqa: E402

SEED = 9
DIGESTS = {
    "program_params":
        "c4e8f2c626a60f72381bad38241d060b8fc35daa5eb4e6f5a50f2c5267b9e12f",
    "reference":
        "c559bebfca2a84f95db442ccb2bce0c8d725b7c1a3304b722966e386b84c1cd2",
    "control":
        "84fa8ebf5a103dabeede82f701b83ce27a18992311de051f969edf5000ceb295",
}


def _config():
    return {"name": "tiny", "program_arch": "yi-6b",
            "program_overrides": {k: _tiny.MODEL[k] for k in (
                "d_model", "n_layers", "n_heads", "n_kv_heads", "head_dim",
                "d_ff", "vocab_size", "rope_theta")},
            "model": _tiny.MODEL}


def _digest(named_arrays) -> str:
    h = hashlib.sha256()
    for name, a in named_arrays:
        a = np.asarray(a)
        h.update(f"{name}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def program_params_digest() -> str:
    import model as MB
    import weights as W
    from repro.model import transformer as T
    c = _config()
    ms = MB.structure(c)
    dt = jnp.bfloat16
    p = W.program_params(T.abstract_params(ms, dt), MB.segments_of(ms),
                         c["model"], SEED, dt)
    flat, _ = jax.tree_util.tree_flatten_with_path(p)
    return _digest((jax.tree_util.keystr(k), v) for k, v in flat)


def logits_digest(quant: bool) -> str:
    import reference as RF
    import weights as W
    rng = np.random.default_rng(SEED)
    seqs = rng.integers(0, _tiny.MODEL["vocab_size"], (3, 24))
    pos = np.tile(np.arange(24), (3, 1))
    out = RF.hidden_logits(_tiny.MODEL, W.seed_key(SEED), seqs, pos,
                           quant=quant)
    return _digest([("logits", out)])


@pytest.mark.parametrize("what", ["program_params", "reference", "control"])
def test_dense_block_is_pinned(what):
    got = {"program_params": program_params_digest,
           "reference": lambda: logits_digest(False),
           "control": lambda: logits_digest(True)}[what]()
    assert got == DIGESTS[what]
