"""Pure-jnp oracle for the fused residual flush: the select-based
block-granularity commit that ``qcache.append_decode`` used before the
kernel existed (quantize the residual, read-modify-write exactly one packed
block per sequence, select against ``full``).  Kept verbatim as the ``xla``
impl and the parity reference for the Pallas path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import layout, quantizer


def residual_flush_ref(
    kw,
    k_scale,
    k_zero,
    vw,
    v_scale,
    v_zero,
    k_res,
    v_res,
    full,
    dest_block,
    *,
    bits: int,
    block_n: int,
    k_gran: str,
    shared_kv: bool,
):
    """Same contract as :func:`..kernel.residual_flush_pallas`.

    kw: int32 [B, H, nb, npr, d_k]; k_res: [B, H, block_n, d_k];
    full/dest_block: int32 [B].  Returns the six packed arrays (V side None
    when ``shared_kv``); sequences with ``full[b] == 0`` are unchanged.
    """
    param_dtype = k_scale.dtype
    if block_n != layout.words_per_block(block_n, bits) * layout.packing_ratio(bits):
        raise ValueError(f"block_n={block_n} violates the layout invariant")

    def one(kw, ks, kz, vw, vs, vz, kres, vres, fl, pb):
        # commit at BLOCK granularity: dynamic_slice one block, select, write
        # back — never a whole-array jnp.where (that would copy the full
        # per-layer cache on every invocation)
        def commit(dst, upd, idx):
            cur = lax.dynamic_slice(dst, idx, upd.shape)
            sel = jnp.where(fl != 0, upd, cur)
            return lax.dynamic_update_slice(dst, sel, idx)

        # kres [H, block_n, d] -> words [H, npr, d]; insert the block dim
        w, s, z = quantizer.quantize_and_pack(
            kres, bits, k_gran, param_dtype=param_dtype
        )
        kw = commit(kw, w[:, None], (0, pb, 0, 0))
        ks = commit(ks, s[:, None], (0, pb, 0))
        kz = commit(kz, z[:, None], (0, pb, 0))
        if not shared_kv:
            wv, sv, zv = quantizer.quantize_and_pack(
                vres, bits, "tensor", param_dtype=param_dtype
            )
            vw = commit(vw, wv[:, None], (0, pb, 0, 0))
            vs = commit(vs, sv[:, None], (0, pb, 0))
            vz = commit(vz, zv[:, None], (0, pb, 0))
        return kw, ks, kz, vw, vs, vz

    if shared_kv:
        dummy = jnp.zeros((kw.shape[0],), jnp.int32)
        kw, ks, kz, _, _, _ = jax.vmap(
            lambda kw, ks, kz, kres, fl, pb, _d: one(
                kw, ks, kz, None, None, None, kres, None, fl, pb
            )
        )(kw, k_scale, k_zero, k_res, full, dest_block, dummy)
        return kw, ks, kz, None, None, None
    return jax.vmap(one)(
        kw, k_scale, k_zero, vw, v_scale, v_zero, k_res, v_res, full, dest_block
    )


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
