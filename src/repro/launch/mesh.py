"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data, model).
Multi-pod: 2 x 16 x 16 = 512 chips (pod, data, model); the pod axis carries
pure data parallelism across the DCI, with optional int8+error-feedback
gradient compression (optim/grad_compress.py).
"""
from __future__ import annotations

import jax


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *, devices=None):
    """A mesh with Auto axes: the models place activations through sharding
    constraints (dist.sharding) and leave the rest to the compiler, the
    sharding model every program in this repo is written for."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_elastic_mesh(*, model_parallel: int = 16):
    """Build the largest valid (data, model) mesh from currently-available
    devices — elastic scaling: after a restart with fewer healthy hosts, the
    same program runs on a smaller data axis and checkpoints reshard on
    restore (checkpoint/manager.py)."""
    n = len(jax.devices())
    model = min(model_parallel, n)
    while n % model:
        model -= 1
    return make_mesh((n // model, model), ("data", "model"))


def pick_batch_axes(mesh, global_batch: int) -> tuple:
    """Largest batch-sharding axis group that divides the global batch."""
    for axes in (("pod", "data"), ("data",), ()):
        if all(a in mesh.axis_names for a in axes):
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if size and global_batch % size == 0:
                return axes
    return ()
