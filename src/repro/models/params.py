"""Parameter-definition trees: shapes + logical sharding axes, materialized
lazily.

Models define a pytree of :class:`P` leaves (shape, logical axes, init).
From that single source of truth we derive:
  * ``shape_tree``   — ShapeDtypeStructs for the dry-run (never allocates);
  * ``init_tree``    — materialized params for smoke tests / real training;
  * ``spec_tree``    — jax.sharding.PartitionSpec per leaf via logical-axis
                       rules (dist/sharding.py), MaxText-style.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class P:
    shape: tuple[int, ...]
    axes: tuple[Any, ...]  # logical axis name (or None) per dim
    init: str = "normal"  # normal | zeros | ones | embed
    dtype: Any = jnp.bfloat16
    scale: float | None = None  # stddev override; default fan-in scaled

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes} rank mismatch")


def stack(defs, n: int, axis_name: str = "layers"):
    """Prepend a scan (layer) dimension to every leaf."""
    return jax.tree.map(
        lambda p: P((n, *p.shape), (axis_name, *p.axes), p.init, p.dtype, p.scale),
        defs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shape_tree(defs):
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
        defs,
        is_leaf=lambda x: isinstance(x, P),
    )


@functools.partial(jax.jit, static_argnums=0)
def _init_leaf(p: P, key):
    # jitted so the f32 draw fuses into the cast: a stacked full-width leaf
    # never exists in f32 on the device
    if p.init == "zeros":
        return jnp.zeros(p.shape, p.dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, p.dtype)
    if p.init == "embed":
        std = p.scale if p.scale is not None else 0.02
        return (jax.random.normal(key, p.shape, jnp.float32) * std).astype(p.dtype)
    # fan-in scaled normal over the last dim by default
    fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
    std = p.scale if p.scale is not None else 1.0 / np.sqrt(max(1, fan_in))
    return (jax.random.normal(key, p.shape, jnp.float32) * std).astype(p.dtype)


def init_tree(defs, rng):
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, P))
    keys = jax.random.split(rng, len(leaves))
    vals = [_init_leaf(p, k) for p, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def spec_tree(defs, rules: dict):
    """Map logical axes -> PartitionSpec via ``rules`` (axis name -> mesh axis
    or tuple of mesh axes or None)."""
    from jax.sharding import PartitionSpec as PS

    def leaf(p: P):
        return PS(*[rules.get(a) for a in p.axes])

    return jax.tree.map(leaf, defs, is_leaf=lambda x: isinstance(x, P))
