"""Logical-axis sharding rules (MaxText-style) and activation constraints.

Parameters declare *logical* axes (models/params.py ``P.axes``); a rules dict
maps logical axis -> mesh axis (or tuple of mesh axes, or None).

Logical axis vocabulary (see :func:`base_rules` for the default mapping onto
the ``("pod", "data", "model")`` mesh):

  ==============  ===========================================  =============
  logical axis    appears on                                   default mesh
  ==============  ===========================================  =============
  ``embed``       d_model dims of projections/embeddings        ``data`` (FSDP)
  ``mlp``         FFN hidden dim                                ``model``
  ``heads``       query-head dim                                ``model``
  ``kv_heads``    KV-head dim (caches too: state_specs)         ``model``
  ``head_dim``    per-head feature dim                          replicated
  ``vocab``       (padded) vocabulary dim                       ``model``
  ``experts``     MoE expert dim                                ``model``
  ``expert_mlp``  per-expert FFN hidden                         replicated
  ``layers``      stacked-layer leading dim (scan axis)         replicated
  ``inner``       nested stack dim (hybrid super-blocks)        replicated
  ==============  ===========================================  =============

Everything here degrades gracefully: axes absent from the mesh are dropped,
dims that a mesh-axis group does not **divide** stay replicated (sharding
never pads — contrast dist.splitkv, which does zero-pad the cache block axis
per call when it must split an indivisible dim), a mesh axis already used by
an earlier dim of the same leaf is dropped, and with no active mesh
:func:`constrain` is a no-op — so the same model code runs on a laptop CPU,
an 8-device fake mesh, and a multi-pod slice unchanged.

The active mesh is the one installed by ``with jax.set_mesh(mesh):``
(published through ``jax.sharding.get_abstract_mesh``).

Layout/spec background: docs/ARCHITECTURE.md §6.
"""
from __future__ import annotations

import math

import jax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.models.params import P


def _active_mesh():
    """The mesh installed by ``with jax.set_mesh(mesh):``, or None outside
    any mesh context."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _axis_entry(entry, mesh, dim_size: int, used: set):
    """Resolve one PartitionSpec entry against the mesh: drop axes that are
    missing, already used in this spec, or whose group does not divide the
    dim."""
    if entry is None:
        return None
    names = tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)
    names = tuple(
        n for n in names
        if n in mesh.axis_names and n not in used and mesh.shape[n] > 1
    )
    if not names:
        return None
    size = math.prod(mesh.shape[n] for n in names)
    if dim_size % size:
        return None
    used.update(names)
    return names if len(names) > 1 else names[0]


def constrain(x, *axes):
    """``with_sharding_constraint`` with per-dim mesh-axis names, tolerant of
    meshes that lack some axes (e.g. no "pod" on a single-pod mesh) and of
    running with no mesh at all (returns x unchanged).

    Trailing dims without an entry stay unconstrained.
    """
    mesh = _active_mesh()
    if mesh is None:
        return x
    used: set = set()
    parts = [
        _axis_entry(a, mesh, x.shape[i], used)
        for i, a in enumerate(axes[: x.ndim])
    ]
    if not any(p is not None for p in parts):
        return x
    return lax.with_sharding_constraint(x, PS(*parts))


def base_rules(cfg) -> dict:
    """Logical axis -> mesh axis mapping for the config's sharding profile.

    ``fsdp_tp`` (default): FSDP over "data" on the embed dim, tensor/expert
    parallelism over "model" on heads/mlp/vocab/experts.  ``tp``: TP only,
    params replicated over "data" ("pod" always carries pure DP).
    """
    fsdp = getattr(cfg, "sharding_profile", "fsdp_tp") != "tp"
    return {
        "embed": "data" if fsdp else None,
        "mlp": "model",
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "vocab": "model",
        "experts": "model",
        "expert_mlp": None,
        "layers": None,
        "inner": None,
    }


def specs_for(defs, rules: dict, mesh) -> object:
    """PartitionSpec per P-leaf: map logical axes through ``rules``, dropping
    entries the mesh cannot honor (missing axis, non-dividing dim, mesh axis
    already used by an earlier dim of the same leaf)."""

    def leaf(p: P):
        used: set = set()
        parts = [
            _axis_entry(rules.get(a), mesh, dim, used)
            for dim, a in zip(p.shape, p.axes)
        ]
        return PS(*parts)

    return jax.tree.map(leaf, defs, is_leaf=lambda x: isinstance(x, P))


def shardings_for(defs, rules: dict, mesh) -> object:
    """NamedShardings for :func:`specs_for` (device_put-ready)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs_for(defs, rules, mesh),
        is_leaf=lambda x: isinstance(x, PS),
    )
