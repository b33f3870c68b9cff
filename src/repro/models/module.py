"""Minimal parameter-tree module system (no flax in this environment).

Parameters are nested dicts whose leaves are :class:`Box` — an array plus its
*logical axis names* (consumed by ``repro.sharding``).  Model init functions
return Box trees; ``unbox``/``axes_of`` split them into a plain value tree
(what apply functions consume) and an axes tree (what the launcher turns into
NamedShardings).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Axes = Tuple[Optional[str], ...]


@dataclass
class Box:
    value: Any            # jax.Array or ShapeDtypeStruct
    axes: Axes

    def __post_init__(self) -> None:
        if hasattr(self.value, "ndim") and len(self.axes) != self.value.ndim:
            raise ValueError(
                f"axes {self.axes} rank != value rank {self.value.shape}"
            )


# Box is a pytree node carrying its axes as static aux data, so init
# functions can run under jax.eval_shape / jit and still return Box trees
# (shape-only lowering never materializes full-model parameters).
jax.tree_util.register_pytree_node(
    Box,
    lambda b: ((b.value,), b.axes),
    lambda axes, children: Box(children[0], axes),
)


def is_box(x: Any) -> bool:
    return isinstance(x, Box)


def unbox(tree):
    return jax.tree.map(lambda b: b.value, tree, is_leaf=is_box)


def axes_of(tree):
    return jax.tree.map(lambda b: b.axes, tree, is_leaf=is_box)


def box_like(values, axes):
    """Zip a value tree and an axes tree back into a Box tree."""
    return jax.tree.map(
        lambda v, a: Box(v, a), values, axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(t, (str, type(None))) for t in x),
    )


# ------------------------------------------------------------- initializers


def normal_init(key, shape, axes: Axes, *, scale: Optional[float] = None,
                dtype=jnp.float32, fan_in: Optional[int] = None) -> Box:
    """Truncated-normal with 1/sqrt(fan_in) scale (fan_in = shape[0] unless
    given)."""
    if scale is None:
        fi = fan_in if fan_in is not None else shape[0]
        scale = 1.0 / math.sqrt(max(fi, 1))
    v = scale * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)
    return Box(v, axes)


def zeros_init(shape, axes: Axes, dtype=jnp.float32) -> Box:
    return Box(jnp.zeros(shape, dtype), axes)


def ones_init(shape, axes: Axes, dtype=jnp.float32) -> Box:
    return Box(jnp.ones(shape, dtype), axes)


def const_init(value, axes: Axes) -> Box:
    return Box(jnp.asarray(value), axes)


class KeyGen:
    """Splitting helper: kg = KeyGen(key); w = init(kg(), ...)."""

    def __init__(self, key):
        self._key = key

    def __call__(self):
        self._key, sub = jax.random.split(self._key)
        return sub


def stack_init(init_fn: Callable[[jax.Array], Any], key, n: int):
    """vmap an init function over n layer keys → stacked Box tree with a
    leading layer axis (axes get a leading None)."""
    keys = jax.random.split(key, n)
    vals = jax.vmap(lambda k: unbox(init_fn(k)))(keys)
    axes = axes_of(init_fn(jax.random.PRNGKey(0)))
    stacked_axes = jax.tree.map(
        lambda a: (None,) + a,
        axes,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(t, (str, type(None))) for t in x),
    )
    return box_like(vals, stacked_axes)


def param_count(tree) -> int:
    vals = unbox(tree) if any(is_box(l) for l in jax.tree.leaves(tree, is_leaf=is_box)) else tree
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(vals))


def cast_tree(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype) if hasattr(x, "astype") else x, tree)
