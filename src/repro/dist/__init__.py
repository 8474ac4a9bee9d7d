"""Distributed layer: sharding rules, decode-state placement, and the
cross-chip split-KV decode path.

Modules:
  * :mod:`repro.dist.sharding`    — logical-axis rules -> PartitionSpecs,
    plus :func:`constrain`, the activation sharding-constraint helper used
    by the models and the train step;
  * :mod:`repro.dist.state_specs` — PartitionSpec trees for decode state
    (dense QuantKVCache and paged PagedQuantKVCache placement, incl. the
    split-KV block-axis / page-table-column sharding);
  * :mod:`repro.dist.splitkv`     — sequence-parallel decode across a mesh
    axis with the logsumexp partials merge (FlashDecoding across chips),
    for both the dense block-sharded and paged table-walk-sharded layouts.
"""
from __future__ import annotations

from repro.dist import sharding, splitkv, state_specs  # noqa: F401
