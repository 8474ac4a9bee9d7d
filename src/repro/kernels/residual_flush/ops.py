"""Public entry point for the fused paged residual flush (quantize+pack+commit)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.residual_flush import kernel as _kernel
from repro.kernels.residual_flush import ref as _ref


def paged_residual_flush(
    kw_pool,
    k_scale_pool,
    k_zero_pool,
    vw_pool,
    v_scale_pool,
    v_zero_pool,
    k_res,
    v_res,
    full,
    dest_page,
    *,
    layer=None,
    bits: int,
    block_n: int,
    k_gran: str,
    shared_kv: bool = False,
    impl: str = "auto",
):
    """The fused residual flush: commit the bf16 residual of every sequence with ``full[b] != 0`` into pool page ``dest_page[b]`` of
    the shared ``[P, H, ...]`` page pools.

    ``layer`` (int32 scalar) makes the pools layer-stacked, ``[L, P, H,
    ...]``: the flush writes layer ``layer`` of them in place and returns the
    whole stack, every other layer bitwise unchanged.  Without it the pools
    are one layer's, flushed as a stack of one.

    The flush gates itself, so callers run it on every decode step
    (``qcache.paged_append_decode``): the kernel quantizes only full rows,
    and the XLA reference runs under a ``lax.cond`` on any full row.  The
    kernel is never put under a ``cond``: on the TPU that would move the
    pools out of fast memory and back around it.  Callers keep the paged
    injectivity contract: ``dest_page`` entries must be pairwise
    distinct.  Callers satisfy it by pointing non-flushing sequences at their
    reserved per-slot scratch page (pool pages ``[0, B)``, never allocated to
    requests — serve/pages.py).  ``shared_kv`` is the MLA latent-pool mode
    (no V-side pools; V operands are ``None``).

    impl: 'pallas' | 'xla' | 'auto' (pallas on TPU, xla elsewhere; a pool
    minor dim that is not lane-aligned raises on TPU: the aliased pools
    cannot be lane-padded in place).
    """
    pools = (kw_pool, k_scale_pool, k_zero_pool, vw_pool, v_scale_pool,
             v_zero_pool)
    if layer is None:
        out = paged_residual_flush(
            *(None if p is None else p[None] for p in pools), k_res, v_res,
            full, dest_page, layer=0, bits=bits, block_n=block_n,
            k_gran=k_gran, shared_kv=shared_kv, impl=impl,
        )
        return tuple(None if p is None else p[0] for p in out)
    layer = jnp.asarray(layer, jnp.int32)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        return _kernel.paged_residual_flush_pallas(
            *pools, k_res, v_res, full, dest_page, layer,
            bits=bits, block_n=block_n, k_gran=k_gran, shared_kv=shared_kv,
            interpret=jax.default_backend() != "tpu",
        )
    if impl == "xla":
        return lax.cond(
            jnp.any(full != 0),
            lambda p: _ref.paged_residual_flush_ref(
                *p, k_res, v_res, full, dest_page, layer,
                bits=bits, block_n=block_n, k_gran=k_gran, shared_kv=shared_kv,
            ),
            lambda p: p, pools,
        )
    raise ValueError(f"unknown impl {impl!r}")
