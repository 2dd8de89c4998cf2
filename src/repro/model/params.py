"""Parameter templates: one source of truth for shape, sharding and init.

Model modules describe their parameters as trees of ``PD`` descriptors
(GLOBAL shapes + PartitionSpec). From a template we derive:
  * initialised arrays            (init_tree)
  * PartitionSpec tree            (pspec_tree)    -> shard_map in_specs
  * abstract ShapeDtypeStructs    (abstract_tree) -> dry-run lowering
so the three can never drift apart.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class PD:
    """Descriptor of one GLOBAL parameter tensor."""

    shape: Tuple[int, ...]
    pspec: P = P()
    init: str = "normal"  # normal | zeros | ones
    fan_in: Optional[int] = None  # None -> last-but-one dim (or last)
    dtype: Any = None  # None -> use the build dtype


def is_pd(x) -> bool:
    return isinstance(x, PD)


def _leaves(tmpl):
    return jax.tree.flatten(tmpl, is_leaf=is_pd)


def _draw(pd: PD, dt, key):
    if pd.init == "zeros":
        return jnp.zeros(pd.shape, dt)
    if pd.init == "ones":
        return jnp.ones(pd.shape, dt)
    fan = pd.fan_in
    if fan is None:
        fan = pd.shape[-2] if len(pd.shape) >= 2 else pd.shape[-1]
    std = fan ** -0.5

    def normal(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    if dt == jnp.float32 or len(pd.shape) < 3:
        return normal(key, pd.shape)
    # A stacked leaf (scan segment / LP pairs) in a narrower dtype: draw one
    # leading slice at a time, so its float32 draw never exists whole.
    return jax.lax.map(lambda k: normal(k, pd.shape[1:]),
                       jax.random.split(key, pd.shape[0]))


@functools.lru_cache(maxsize=None)
def _leaf_program(pd: PD, dt, sharding):
    return jax.jit(functools.partial(_draw, pd, dt), out_shardings=sharding)


def init_tree(tmpl, key, dtype=jnp.float32, mesh=None):
    """Initialised arrays for a template, each leaf made by its own jitted
    program directly in its dtype. With ``mesh`` every leaf is made already
    sharded by its pspec, so no device ever holds a whole leaf it does not
    own."""
    leaves, treedef = _leaves(tmpl)
    keys = jax.random.split(key, max(len(leaves), 1))
    out = []
    for pd, k in zip(leaves, keys):
        sh = NamedSharding(mesh, pd.pspec) if mesh is not None else None
        out.append(_leaf_program(pd, jnp.dtype(pd.dtype or dtype), sh)(k))
    return treedef.unflatten(out)


def pspec_tree(tmpl):
    leaves, treedef = _leaves(tmpl)
    return treedef.unflatten([pd.pspec for pd in leaves])


def abstract_tree(tmpl, dtype=jnp.bfloat16):
    leaves, treedef = _leaves(tmpl)
    return treedef.unflatten(
        [jax.ShapeDtypeStruct(pd.shape, pd.dtype or dtype) for pd in leaves]
    )


# -- structural helpers ------------------------------------------------------

def stack_tmpl(tmpl, n: int):
    """Template for ``n`` stacked copies (scan segments / LP pairs): prepend a
    replicated leading axis to every descriptor."""

    def bump(pd: PD) -> PD:
        return PD(
            shape=(n, *pd.shape),
            pspec=P(None, *pd.pspec),
            init=pd.init,
            fan_in=pd.fan_in if pd.fan_in is not None else (pd.shape[-2] if len(pd.shape) >= 2 else pd.shape[-1]),
            dtype=pd.dtype,
        )

    return jax.tree.map(bump, tmpl, is_leaf=is_pd)


def stack_trees(trees):
    """Stack a list of identical-structure param trees along a new axis 0."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def unstack_tree(tree, n: int):
    return [jax.tree.map(lambda x: x[i], tree) for i in range(n)]
