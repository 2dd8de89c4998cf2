"""Random weights for a benchmark configuration, made from the seed.

Every weight of layer ``i`` is drawn from ``fold_in(fold_in(key, i + 1),
leaf)``, ``leaf`` the leaf's draw id (``leaf_ids``): the sum of the four
bytes of one random 32-bit word, centred (an integer ``s`` in [-510, 510]
of standard deviation 147.8, close to normal), then ``(s + shift) *
scale`` with an integer ``shift`` and a float32 ``scale``, rounded to the
served dtype. By default a matrix has ``shift`` 0 and ``scale`` ``fan_in
** -0.5 / 147.8``, and a vector is a norm scale, ``(s + 1478) / 1478``,
about ``1 + 0.1 * normal``; a part may state another ``(shift, scale)``
for a leaf (``RULES``). Integer arithmetic and one float multiply give the
same bits in every program and on every backend, however the compiler
fuses them. The draw depends only on the seed, the layer, the leaf name
and its shape, never on how a program lays its parameters out. So the
program under test is handed these weights the way it would load a
checkpoint (``program_params``), and the reference (``reference.py``)
draws each layer again from the seed when it needs it, taking nothing
from the program.

A layer's leaves are those of its parts (``bench/parts/``; ``attn`` and
``mlp`` make the Llama layout, ``x @ w``): ``ln1 [D]``, ``wq [D, Hq*hd]``,
``wk``/``wv [D, Hkv*hd]``, ``wo [Hq*hd, D]``, ``ln2 [D]``,
``w_gate``/``w_up [D, F]``, ``w_down [F, D]``. Top level: ``tok [V, D]``,
``head [D, V]`` (absent when tied) and ``final_norm [D]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import spec

#: The leaves of the first block this module knew, and the top-level
#: ones: their draw ids (0-11) never change. A part's new leaves take the
#: ids its ``IDS`` declares, after these (``leaf_ids``).
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


@functools.lru_cache(maxsize=None)
def leaf_ids() -> dict:
    """Draw id of every leaf name: ``_LEAF_ID``'s, then those each part
    declares for the leaves it adds (``IDS``). One id per name and one
    name per id, so that no two leaves ever draw alike."""
    ids = dict(_LEAF_ID)
    for part in spec.all_parts():
        for name, i in getattr(part, "IDS", {}).items():
            if ids.get(name, i) != i or (name not in ids
                                         and i in ids.values()):
                raise ValueError(f"part {part.__name__}: leaf {name!r} id "
                                 f"{i} clashes with {ids}")
            ids[name] = i
    return ids


def kind_leaves(m: dict, kind: tuple) -> dict:
    """``{leaf: (canonical shape, (shift, scale) or None)}`` of one layer
    kind ``(mixer, ffn)``, the mixer's leaves first."""
    out = {}
    for part in spec.parts_of(kind):
        rules = getattr(part, "RULES", {})
        for name, shape in part.shapes(m).items():
            if name in out or name not in leaf_ids():
                raise ValueError(f"layer {kind}: leaf {name!r} is named "
                                 "twice or has no draw id")
            out[name] = (tuple(shape), rules.get(name))
    return out


def top_shapes(m: dict) -> dict:
    D, V = m["d_model"], m["vocab_size"]
    out = {"tok": (V, D), "final_norm": (D,)}
    if not m["tie_embeddings"]:
        out["head"] = (D, V)
    return out


#: Standard deviation of a sum of four independent uniform bytes.
BYTE_SUM_SD = (4 * (256 ** 2 - 1) / 12) ** 0.5


def affine(mean: float, sd: float) -> tuple:
    """The ``(shift, scale)`` rule of a leaf drawn about ``mean`` with
    standard deviation ``sd`` (the mean is met to within ``scale / 2``)."""
    scale = sd / BYTE_SUM_SD
    return round(mean / scale), scale


def draw(key, layer, name: str, shape, dtype, rule=None):
    """One leaf of one layer (``layer`` may be traced); ``rule``: the
    leaf's ``(shift, scale)``, None for the default by its rank."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                           leaf_ids()[name])
    b = jax.random.bits(k, shape, jnp.uint32)
    s = sum(((b >> (8 * i)) & 0xFF).astype(jnp.int32) for i in range(4)) - 510
    if rule is None and len(shape) == 1:                  # a norm scale
        rule = 1478, 1 / 1478
    elif rule is None:
        rule = 0, (shape[1] if name == "tok" else shape[0]) ** -0.5 \
            / BYTE_SUM_SD
    shift, scale = rule
    if shift:
        s = s + shift
    return (s.astype(jnp.float32) * jnp.float32(scale)).astype(dtype)


def layer_weights(key, layer, m: dict, dtype, kind=None):
    """Every leaf of layer ``layer``; ``kind``, its ``(mixer, ffn)``, is
    needed where ``layer`` is traced."""
    kind = kind or spec.layer_kind(m, layer)
    return {n: draw(key, layer, n, s, dtype, r)
            for n, (s, r) in kind_leaves(m, kind).items()}


def top_weights(key, m: dict, dtype):
    return {n: draw(key, TOP, n, s, dtype) for n, s in top_shapes(m).items()}


def _canonical_name(path) -> str:
    """Top-level program leaf path -> canonical leaf name: the last key,
    except a norm's ``scale``, which takes its parent's name."""
    keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
    name = keys[-1]
    if name == "scale":
        name = keys[-2]
    return name


def program_names(kind: tuple) -> dict:
    """Path of each leaf of layer kind ``kind`` in the program's layer
    tree (its parts' ``PROGRAM``, ``"attn/wq"``) -> the leaf's name."""
    return {tuple(path.split("/")): name for part in spec.parts_of(kind)
            for name, path in part.PROGRAM.items()}


def program_params(abstract, segments, m: dict, seed: int, dtype):
    """The program's parameter tree, filled with this module's weights in
    ONE jitted call on the default device.

    ``abstract``: the program's abstract parameter tree (``{"embed":
    {"tok", ["head"]}, "final_norm": {"scale"}, "segments": [...]}``);
    ``segments``: its scan segments, each ``(count, pair, first_layer)``.
    A segment leaf is ``[count, (2,) *shape]`` (no count axis when the
    count is 1); group ``c`` of a pair
    segment holds layers ``first + 2c`` and ``first + 2c + 1``. A leaf no
    part of its layer names, a part's leaf the program lacks, a segment
    whose layers are of different kinds, or a trailing shape that differs
    from the canonical one, is an error: the program's layout has
    changed, or the configuration's pattern is not the program's."""
    tshape = top_shapes(m)
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    plan = []
    seen = {}                 # segment -> (its leaves, names the program has)
    for path, leaf in flat:
        where = jax.tree_util.keystr(path)
        top = getattr(path[0], "key", None)
        if top == "segments":
            count, pair, first = segments[path[1].idx]
            step = 2 if pair else 1
            ids = first + np.arange(count)[:, None] * step \
                + np.arange(step)[None, :]
            kinds = {spec.layer_kind(m, int(i)) for i in ids.flat}
            if len(kinds) > 1:
                raise ValueError(f"program segment of layers {ids.ravel()} "
                                 f"holds layers of kinds {sorted(kinds)}")
            kind = kinds.pop()
            leaves = kind_leaves(m, kind)
            name = program_names(kind).get(
                tuple(getattr(p, "key", None) for p in path[2:]))
            if name is None:
                raise ValueError(f"program leaf {where} is named by no part "
                                 f"of layer {first}, {kind}")
            seen.setdefault(path[1].idx, (leaves, set()))[1].add(name)
            # A segment of one group has no count axis.
            lead = ((count,) if count > 1 else ()) + ((2,) if pair else ())
            want = lead + leaves[name][0]
            if tuple(leaf.shape) != want:
                raise ValueError(f"program leaf {where} {leaf.shape} is not "
                                 f"canonical {name} {want}")
            plan.append(("layer", name, ids.reshape(lead or (1,)),
                         leaves[name]))
        else:
            name = _canonical_name(path)
            if name not in tshape or tuple(leaf.shape) != tshape[name]:
                raise ValueError(f"program leaf {where} {leaf.shape} is not "
                                 f"canonical {name}")
            plan.append(("top", name, None, None))
    for idx, (leaves, names) in seen.items():
        if set(leaves) != names:
            raise ValueError(f"program segment {idx} lacks the leaves "
                             f"{sorted(set(leaves) - names)}")

    leaf_shapes = [tuple(leaf.shape) for _, leaf in flat]

    def make(key):
        out = []
        for level, name, ids, leaf in plan:
            if level == "top":
                out.append(draw(key, TOP, name, tshape[name], dtype))
                continue
            shape, rule = leaf
            flat_ids = jnp.asarray(ids.reshape(-1), jnp.int32)
            # One layer's float32 draw at a time, never the whole stack.
            w = jax.lax.map(
                lambda i, n=name, s=shape, r=rule: draw(key, i, n, s, dtype,
                                                        r), flat_ids)
            out.append(w.reshape(leaf_shapes[len(out)]))
        return treedef.unflatten(out)

    return jax.jit(make)(seed_key(seed))
