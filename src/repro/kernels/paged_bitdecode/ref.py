"""Oracle for paged low-bit decode attention: gather pages, then reuse the
dense bitdecode reference (which also owns the shared_kv latent semantics)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.bitdecode import ref as bd_ref


def _gather(pool, table, layer):
    """pool [L, P, H, ...] + table [B, nb] -> layer ``layer``'s pages,
    [B, H, nb, ...], in one gather (no copy of the layer's pool)."""
    if pool is None:  # shared_kv: no V-side pools
        return None
    g = pool[layer, table]  # [B, nb, H, ...]
    return jnp.moveaxis(g, 2, 1)


def paged_bitdecode_attention_ref(
    q,
    kw_pool, k_scale_pool, k_zero_pool,   # [L,P,H,npr,dk], [L,P,H,dk|block]
    vw_pool, v_scale_pool, v_zero_pool,   # None when shared_kv
    k_res, v_res,                          # dense residual per sequence
    page_table,                            # int32 [B, nb_max]
    pack_blocks, res_len,
    layer,                                 # int32 scalar
    *,
    bits, block_n=128, sm_scale=None, k_gran="channel",
    shared_kv=False, d_v=None, num_splits=1, draft_bits=None,
):
    kw, ks, kz, vw, vs, vz = (
        _gather(p, page_table, layer)
        for p in (kw_pool, k_scale_pool, k_zero_pool, vw_pool, v_scale_pool,
                  v_zero_pool))
    return bd_ref.bitdecode_attention_ref(
        q, kw, ks, kz, vw, vs, vz, k_res, v_res, pack_blocks, res_len,
        bits=bits, block_n=block_n, sm_scale=sm_scale, k_gran=k_gran,
        shared_kv=shared_kv, d_v=d_v, num_splits=num_splits,
        draft_bits=draft_bits,
    )
