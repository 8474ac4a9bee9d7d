"""PartitionSpec trees for decode state (the serving-side face of sharding).

Decode state is a pytree of stacked PagedQuantKVCache dataclasses plus
per-model recurrent state (SSM/xLSTM) and a position vector.  Placement
policy:

  * batch dims shard over the largest ("pod", "data") group that divides the
    global batch (mirrors launch/mesh.pick_batch_axes);
  * the KV-head dim of pools and residuals shards over "model" (TP decode);
  * when ``seq_ax`` is given (long-context small-batch shapes, where the
    batch group is empty), the page-table columns shard along it — the
    at-rest layout matching repro.dist.splitkv's sharded walk — and, under
    page affinity, the pools' page dim too.

Leaves that are not cache fields (pos, SSM states, ...) shard their batch
dim, identified as the first dim equal to ``global_batch`` — a heuristic,
but a safe one: specs only place data, they never change semantics.

Cache-field roles map onto the PagedQuantKVCache shapes of
docs/ARCHITECTURE.md §7, shifted right by the model's stacking dims
(layers, super-blocks).  Axis names are physical mesh axes
(``"pod"/"data"/"model"`` plus the caller's ``seq_ax``), matching
dist.sharding's :func:`~repro.dist.sharding.base_rules` targets for the
same tensors.  Like dist.sharding, placement never pads: an axis group that
does not divide a dim is dropped (the leaf stays replicated on that dim).

Specs are consumed via ``jax.device_put`` / shardings built under
``jax.set_mesh``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
from jax.sharding import PartitionSpec as PS

from repro.core.qcache import PagedQuantKVCache

# field -> (base rank without stacking dims, {base-dim index: role}).  By
# default the pools ([P, H, ...]) replicate their page dim (pages are
# scattered arbitrarily, only the table *walk* is sequence-parallel — see
# dist.splitkv.splitkv_paged_decode_attention) and shard KV heads over
# "model"; the page_table columns carry the "blocks"
# role so the at-rest placement matches the sharded walk.  Prefix sharing
# rides this placement unchanged: a shared page id may appear in several
# table rows (or twice in one row's shard), and because every chip holds
# the full pools each shard dereferences it locally — sharing needs no
# cross-chip coordination, and copy-on-write repoints are plain table
# updates under the same spec.
_PAGED_FIELD_ROLES = {
    "kw": (4, {1: "heads"}),
    "k_scale": (3, {1: "heads"}),
    "k_zero": (3, {1: "heads"}),
    "vw": (4, {1: "heads"}),
    "v_scale": (3, {1: "heads"}),
    "v_zero": (3, {1: "heads"}),
    "k_res": (4, {0: "batch", 1: "heads"}),
    "v_res": (4, {0: "batch", 1: "heads"}),
    "page_table": (2, {0: "batch", 1: "blocks"}),
    "pack_blocks": (1, {0: "batch"}),
    "res_len": (1, {0: "batch"}),
}

# Page-affine layout (docs/SERVING.md §14): the pools' leading (page) dim
# ALSO shards along ``seq_ax``, matching the allocator contract of
# serve/pages.py (``shards`` = axis size): the page backing table column j
# lives only on the chip that walks column j, so aggregate pool bytes scale
# linearly with the mesh.  Residuals stay batch/heads-placed (slot-indexed),
# and the table keeps its column sharding.
_PAGED_AFFINE_FIELD_ROLES = {
    **_PAGED_FIELD_ROLES,
    "kw": (4, {0: "pages", 1: "heads"}),
    "k_scale": (3, {0: "pages", 1: "heads"}),
    "k_zero": (3, {0: "pages", 1: "heads"}),
    "vw": (4, {0: "pages", 1: "heads"}),
    "v_scale": (3, {0: "pages", 1: "heads"}),
    "v_zero": (3, {0: "pages", 1: "heads"}),
}


def _batch_axes(mesh, global_batch: int) -> tuple:
    """Largest batch-sharding axis group that divides the global batch."""
    for axes in (("pod", "data"), ("data",), ()):
        if all(a in mesh.axis_names for a in axes):
            size = math.prod(mesh.shape[a] for a in axes)
            if size and global_batch % size == 0:
                return axes
    return ()


def _entry(names, mesh, dim: int):
    names = tuple(n for n in names if n in mesh.axis_names and mesh.shape[n] > 1)
    if not names or dim % math.prod(mesh.shape[n] for n in names):
        return None
    return names if len(names) > 1 else names[0]


def _cache_specs(c, mesh, batch_axes, seq_ax, page_affine=False):
    role_axes = {
        "batch": batch_axes,
        "heads": ("model",),
        "blocks": (seq_ax,) if seq_ax else (),
        "pages": (seq_ax,) if seq_ax else (),
    }
    roles_table = _PAGED_AFFINE_FIELD_ROLES if page_affine else _PAGED_FIELD_ROLES

    def field_spec(name: str, arr):
        if arr is None:
            return None
        base_rank, roles = roles_table[name]
        lead = arr.ndim - base_rank  # stacked layer dims stay replicated
        parts = [None] * arr.ndim
        used: set = set()  # a mesh axis may appear once per PartitionSpec
        for i, role in sorted(roles.items()):
            e = _entry(role_axes[role], mesh, arr.shape[lead + i])
            names = e if isinstance(e, tuple) else (e,) if e else ()
            if any(n in used for n in names):
                continue  # earlier dim claimed the axis; stay replicated
            used.update(names)
            parts[lead + i] = e
        return PS(*parts)

    kwargs = {name: field_spec(name, getattr(c, name)) for name in roles_table}
    return dataclasses.replace(c, **kwargs)


def decode_state_specs(model, mesh, *, global_batch: int, seq_ax: str | None = None,
                       paged: bool = False, n_pages: int | None = None,
                       nb_max: int | None = None, page_affine: bool = False):
    """PartitionSpec tree matching ``model.init_decode_state`` structure
    (or ``model.init_paged_decode_state`` when ``paged``).

    ``page_affine`` (paged only) additionally shards the pools' page dim
    along ``seq_ax`` — pair with serve/pages.py's sharded allocator and
    ``splitkv_paged_decode_attention(page_affine=True)``.  Placement drops
    an axis whose size does not divide the *probed* dim, so callers whose
    real state differs from the default probe shape (the serve engine's
    mesh-aligned ``nb_max``, its pool size) must pass ``nb_max`` /
    ``n_pages`` explicitly."""
    cfg = model.cfg
    batch_axes = _batch_axes(mesh, global_batch)
    # structure only — nb just has to be positive; actual decode states may
    # have any block count, specs are rank/dim-role based.  Divisibility is
    # checked against these probe dims though, so nb_max/n_pages overrides
    # matter whenever an axis must actually split the dim (page_affine).
    if nb_max is None:
        nb_max = 4
    max_seq = nb_max * getattr(cfg, "kv_block", 128)
    # closure (not args) so batch/max_seq stay concrete python ints
    if paged:
        np_ = n_pages if n_pages is not None else global_batch * (nb_max + 1)
        state = jax.eval_shape(
            lambda: model.init_paged_decode_state(
                global_batch, n_pages=np_, nb_max=nb_max
            )
        )
    else:
        state = jax.eval_shape(lambda: model.init_decode_state(global_batch, max_seq))

    def generic(arr):
        parts = [None] * arr.ndim
        if batch_axes:
            for i, d in enumerate(arr.shape):
                if d == global_batch:
                    parts[i] = _entry(batch_axes, mesh, d)
                    break
        return PS(*parts)

    def node(x):
        if isinstance(x, PagedQuantKVCache):
            return _cache_specs(x, mesh, batch_axes, seq_ax, page_affine)
        return generic(x)

    return jax.tree.map(
        node, state, is_leaf=lambda x: isinstance(x, PagedQuantKVCache)
    )
