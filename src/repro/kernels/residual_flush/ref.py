"""Pure-jnp oracle for the fused paged residual flush: quantize every
residual, read-modify-write exactly one pool page per sequence, select
against ``full``.  The ``xla`` impl and the parity reference for the Pallas
path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import layout, quantizer


def paged_residual_flush_ref(
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
    layer,
    *,
    bits: int,
    block_n: int,
    k_gran: str,
    shared_kv: bool = False,
):
    """Oracle for :func:`..kernel.paged_residual_flush_pallas`: quantize every
    residual, gather the current destination pages of layer ``layer``, select
    against ``full``, scatter back.  Same injectivity contract as the kernel:
    ``dest_page`` entries must be pairwise distinct (non-flushing sequences
    point at their reserved per-slot scratch page), so the scatter has no
    duplicate indices.

    kw_pool: int32 [L, P, H, npr, d_k]; k_res: [B, H, block_n, d_k];
    full/dest_page: int32 [B]; layer: int32 scalar.  Returns the six updated
    pool arrays (V side ``None`` when ``shared_kv`` — the MLA latent pools
    have no V stream).
    """
    param_dtype = k_scale_pool.dtype
    if block_n != layout.words_per_block(block_n, bits) * layout.packing_ratio(bits):
        raise ValueError(f"block_n={block_n} violates the layout invariant")
    dest = jnp.minimum(dest_page.astype(jnp.int32), kw_pool.shape[1] - 1)
    fl = full != 0

    w, s, z = jax.vmap(
        lambda r: quantizer.quantize_and_pack(r, bits, k_gran, param_dtype=param_dtype)
    )(k_res)

    def commit(pool, new):
        cur = pool[layer, dest]
        keep = fl.reshape((-1,) + (1,) * (new.ndim - 1))
        return pool.at[layer, dest].set(jnp.where(keep, new.astype(pool.dtype), cur))

    if shared_kv:
        return (
            commit(kw_pool, w),
            commit(k_scale_pool, s),
            commit(k_zero_pool, z),
            None, None, None,
        )

    wv, sv, zv = jax.vmap(
        lambda r: quantizer.quantize_and_pack(r, bits, "tensor", param_dtype=param_dtype)
    )(v_res)
    return (
        commit(kw_pool, w),
        commit(k_scale_pool, s),
        commit(k_zero_pool, z),
        commit(vw_pool, wv),
        commit(v_scale_pool, sv),
        commit(v_zero_pool, zv),
    )
