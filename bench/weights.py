"""Random weights for a benchmark configuration, made from the seed.

Every weight of layer ``i`` is drawn from ``fold_in(fold_in(key, i + 1),
leaf)``: the sum of the four bytes of one random 32-bit word, centred
(an integer in [-510, 510] of standard deviation 147.8, close to normal),
times ``fan_in ** -0.5 / 147.8`` and rounded to the served dtype; norm
scales are ``(s + 1478) / 1478``, about ``1 + 0.1 * normal``. Integer
arithmetic and one float multiply give the same bits in every program and
on every backend, however the compiler fuses them. The draw depends only
on the seed, the layer, the leaf name and its shape, never on how a
program lays its parameters out. So the program under test is handed these weights the
way it would load a checkpoint (``program_params``), and the reference
(``reference.py``) draws each layer again from the seed when it needs it,
taking nothing from the program.

Canonical leaves of one decoder layer (Llama layout, ``x @ w``):
``ln1 [D]``, ``wq [D, Hq*hd]``, ``wk``/``wv [D, Hkv*hd]``, ``wo [Hq*hd, D]``,
``ln2 [D]``, ``w_gate``/``w_up [D, F]``, ``w_down [F, D]``. Top level:
``tok [V, D]``, ``head [D, V]`` (absent when tied) and ``final_norm [D]``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up",
                "w_down")
TOP_LEAVES = ("tok", "head", "final_norm")
_LEAF_ID = {n: i for i, n in enumerate(LAYER_LEAVES + TOP_LEAVES)}
#: Layer index used for the top-level leaves' keys.
TOP = -1


def seed_key(seed: int):
    """A threefry key from any whole number below 2**64."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def layer_shapes(m: dict) -> dict:
    """Canonical shapes of one layer's leaves for model dict ``m``."""
    D, F, hd = m["d_model"], m["d_ff"], m["head_dim"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return {"ln1": (D,), "wq": (D, q), "wk": (D, kv), "wv": (D, kv),
            "wo": (q, D), "ln2": (D,), "w_gate": (D, F), "w_up": (D, F),
            "w_down": (F, D)}


def top_shapes(m: dict) -> dict:
    D, V = m["d_model"], m["vocab_size"]
    out = {"tok": (V, D), "final_norm": (D,)}
    if not m["tie_embeddings"]:
        out["head"] = (D, V)
    return out


#: Standard deviation of a sum of four independent uniform bytes.
BYTE_SUM_SD = (4 * (256 ** 2 - 1) / 12) ** 0.5


def draw(key, layer, name: str, shape, dtype):
    """One leaf of one layer (``layer`` may be traced)."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1), _LEAF_ID[name])
    b = jax.random.bits(k, shape, jnp.uint32)
    s = sum(((b >> (8 * i)) & 0xFF).astype(jnp.int32) for i in range(4)) - 510
    if len(shape) == 1:                                   # a norm scale
        return ((s + 1478).astype(jnp.float32)
                * jnp.float32(1 / 1478)).astype(dtype)
    fan_in = shape[1] if name == "tok" else shape[0]
    c = jnp.float32(fan_in ** -0.5 / BYTE_SUM_SD)
    return (s.astype(jnp.float32) * c).astype(dtype)


def layer_weights(key, layer, m: dict, dtype):
    return {n: draw(key, layer, n, s, dtype)
            for n, s in layer_shapes(m).items()}


def top_weights(key, m: dict, dtype):
    return {n: draw(key, TOP, n, s, dtype) for n, s in top_shapes(m).items()}


def _canonical_name(path) -> str:
    """Program leaf path -> canonical leaf name: the last key, except a
    norm's ``scale``, which takes its parent's name."""
    keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
    name = keys[-1]
    if name == "scale":
        name = keys[-2]
    return name


def program_params(abstract, segments, m: dict, seed: int, dtype):
    """The program's parameter tree, filled with this module's weights in
    ONE jitted call on the default device.

    ``abstract``: the program's abstract parameter tree (``{"embed":
    {"tok", ["head"]}, "final_norm": {"scale"}, "segments": [...]}``);
    ``segments``: its scan segments, each ``(count, pair, first_layer)``.
    A segment leaf is ``[count, (2,) *shape]`` (no count axis when the
    count is 1); group ``c`` of a pair
    segment holds layers ``first + 2c`` and ``first + 2c + 1``. Any leaf
    this module does not know, or whose trailing shape differs from the
    canonical one, is an error: the program's layout has changed."""
    lshape = layer_shapes(m)
    tshape = top_shapes(m)
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    plan = []
    for path, leaf in flat:
        name = _canonical_name(path)
        top = getattr(path[0], "key", None)
        if top == "segments":
            count, pair, first = segments[path[1].idx]
            # A segment of one group has no count axis.
            lead = ((count,) if count > 1 else ()) + ((2,) if pair else ())
            want = lead + tuple(lshape.get(name, ()))
            if name not in lshape or tuple(leaf.shape) != want:
                raise ValueError(f"program leaf {jax.tree_util.keystr(path)}"
                                 f" {leaf.shape} is not canonical {name} "
                                 f"{want}")
            step = 2 if pair else 1
            ids = first + np.arange(count)[:, None] * step \
                + np.arange(step)[None, :]
            plan.append(("layer", name, ids.reshape(lead or (1,))))
        else:
            if name not in tshape or tuple(leaf.shape) != tshape[name]:
                raise ValueError(f"program leaf {jax.tree_util.keystr(path)}"
                                 f" {leaf.shape} is not canonical {name}")
            plan.append(("top", name, None))

    leaf_shapes = [tuple(leaf.shape) for _, leaf in flat]

    def make(key):
        out = []
        for kind, name, ids in plan:
            if kind == "top":
                out.append(draw(key, TOP, name, tshape[name], dtype))
                continue
            flat_ids = jnp.asarray(ids.reshape(-1), jnp.int32)
            # One layer's float32 draw at a time, never the whole stack.
            w = jax.lax.map(
                lambda i, n=name: draw(key, i, n, lshape[n], dtype), flat_ids)
            out.append(w.reshape(leaf_shapes[len(out)]))
        return treedef.unflatten(out)

    return jax.jit(make)(seed_key(seed))
