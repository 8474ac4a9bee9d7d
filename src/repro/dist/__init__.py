"""Distributed layer: sharding rules, decode-state placement, and the
cross-chip split-KV decode path.

Modules:
  * :mod:`repro.dist.sharding`    — logical-axis rules -> PartitionSpecs,
    plus :func:`constrain`, the activation sharding-constraint helper used
    by the models and the train step;
  * :mod:`repro.dist.state_specs` — PartitionSpec trees for decode state
    (PagedQuantKVCache placement, incl. the split-KV page-table-column and
    page-affine pool sharding);
  * :mod:`repro.dist.splitkv`     — sequence-parallel decode across a mesh
    axis with the logsumexp partials merge (FlashDecoding across chips):
    the page-table walk sharded, and the paged flush under ``shard_map``.
"""
from __future__ import annotations

from repro.dist import sharding, splitkv, state_specs  # noqa: F401
