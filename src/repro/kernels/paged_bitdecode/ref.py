"""Pure-jnp oracle for paged low-bit decode attention: gather the pages a
page table names, then run :func:`bitdecode_attention_ref` over them.

Also serves as the XLA fallback path on CPU and the dry-run lowering target:
it performs the *same* work (unpack, dequant, QK^T, online-softmax-equivalent
masked softmax, PV) as the Pallas kernel, so ``cost_analysis()`` of a program
built on this path reflects the mixed-precision pipeline honestly.

``num_splits > 1`` runs the split-KV (FlashDecoding) semantics: per-split
masked-softmax partials over contiguous block ranges (residual tail owned by
the last split), combined with the logsumexp merge — the oracle for both the
in-kernel split grid and the cross-chip repro.dist.splitkv layer.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from repro.core import layout, quantizer
from repro.kernels.paged_bitdecode.kernel import merge_partials

MASK_VALUE = -1e37


def _dequant_blocks(words, scale, zero, bits, granularity, dtype=jnp.bfloat16,
                    draft_bits=None):
    """words [B,H,nb,npr,d] -> [B,H,nb*block_n,d] in natural token order.

    ``draft_bits`` (speculative draft read, QuantSpec-style): dequantize as if
    only the top ``draft_bits`` of each ``bits``-bit code had been stored —
    ``q >> (bits - draft_bits)`` against a scale widened by ``2^(bits -
    draft_bits)``.  Same packed words, same (scale, zero) metadata, no second
    cache: just a cheaper *read* of the committed pool that the verify pass
    re-reads at full fidelity.
    """
    if draft_bits is not None and draft_bits < bits:
        shift = bits - draft_bits
        q = layout.unpack_strided(words, bits) >> shift
        x = quantizer.dequantize_block(
            q, scale.astype(jnp.float32) * (1 << shift), zero, granularity,
            dtype=dtype,
        )
    else:
        x = quantizer.unpack_and_dequantize(words, scale, zero, bits, granularity, dtype=dtype)
    b, h, nb, n, d = x.shape
    return x.reshape(b, h, nb * n, d)


def _softmax_partial(scores, v_all):
    """Masked-softmax partial over the last (token) axis: (o, lse).

    Fully-masked rows (empty split) produce o = 0 and lse ~ -inf — the same
    l=0 guard the Pallas ``finalize`` applies, so the merge drops them."""
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = lax.dot_general(
        p.astype(jnp.bfloat16),
        v_all,
        (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    )
    out = out / l.astype(jnp.float32)
    lse = (m + jnp.log(l))[..., 0]
    return out, lse


def bitdecode_attention_ref(
    q,
    kw,
    k_scale,
    k_zero,
    vw,
    v_scale,
    v_zero,
    k_res,
    v_res,
    pack_blocks,
    res_len,
    *,
    bits: int,
    block_n: int = 128,
    sm_scale: float | None = None,
    k_gran: str = "channel",
    shared_kv: bool = False,
    d_v: int | None = None,
    num_splits: int = 1,
    draft_bits: int | None = None,
):
    """Low-bit flash-decode attention over one sequence's own blocks,
    reference semantics.

    q: [B, H_kv, g_q, d_k]    (already query-transformed: g_q = h_q / h_kv)
    kw: int32 [B, H_kv, nb, npr, d_k]; k params per k_gran.
    vw: int32 [B, H_kv, nb, npr, d_v] + per-token params [B,H,nb,block_n]
        (ignored when shared_kv: V is the first d_v channels of dequant K —
        the MLA latent-cache mode).
    k_res/v_res: bf16 [B, H_kv, N_r, d_k/d_v]; pack_blocks/res_len: int32 [B].
    num_splits: split-KV partition count (1 = classic single-pass softmax).
    draft_bits: speculative draft read — dequantize the packed cache at a
    truncated bit-width (see :func:`_dequant_blocks`); the bf16 residual is
    read at full fidelity either way.

    Returns (out [B,H,g,d_v] f32, lse [B,H,g] f32).
    """
    b, h, g, d_k = q.shape
    nb = kw.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / (d_k**0.5)
    if shared_kv:
        assert d_v is not None
    else:
        d_v = v_res.shape[-1]
    if draft_bits is not None and not 1 <= draft_bits <= bits:
        raise ValueError(f"draft_bits={draft_bits} outside [1, bits={bits}]")

    k_hat = _dequant_blocks(kw, k_scale, k_zero, bits, k_gran,
                            draft_bits=draft_bits)  # [B,H,Sp,dk]
    if shared_kv:
        v_hat = k_hat[..., :d_v]
        if v_res is None:  # latent mode: residual V is the slice of residual K
            v_res = k_res[..., :d_v]
    else:
        v_hat = _dequant_blocks(vw, v_scale, v_zero, bits, "tensor",
                                draft_bits=draft_bits)

    k_all = jnp.concatenate([k_hat, k_res.astype(k_hat.dtype)], axis=2)
    v_all = jnp.concatenate([v_hat, v_res.astype(v_hat.dtype)], axis=2)

    s_pack = nb * block_n
    res_n = k_res.shape[2]
    t = jnp.arange(s_pack + res_n, dtype=jnp.int32)
    valid_pack = t[None, :] < (pack_blocks[:, None] * block_n)
    in_res = t[None, :] >= s_pack
    valid_res = in_res & (t[None, :] - s_pack < res_len[:, None])
    valid = jnp.where(in_res, valid_res, valid_pack)  # [B, S_tot]

    scores = lax.dot_general(
        q.astype(jnp.bfloat16),
        k_all,
        (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    ) * sm_scale  # [B,H,g,S_tot]

    num_splits = max(1, min(num_splits, nb))
    if num_splits == 1:
        scores = jnp.where(valid[:, None, None, :], scores, MASK_VALUE)
        return _softmax_partial(scores, v_all)

    # split-KV oracle: split i owns packed blocks [i*bps, (i+1)*bps); the
    # residual tail rides with the last split.  Partials per split, then the
    # logsumexp merge (identical math to kernel.merge_partials).
    bps = -(-nb // num_splits)
    parts_o, parts_lse = [], []
    for i in range(num_splits):
        lo, hi = i * bps * block_n, min((i + 1) * bps, nb) * block_n
        own = (t[None, :] >= lo) & (t[None, :] < hi)
        if i == num_splits - 1:
            own = own | in_res
        mask = valid & own
        s_i = jnp.where(mask[:, None, None, :], scores, MASK_VALUE)
        o_i, lse_i = _softmax_partial(s_i, v_all)
        parts_o.append(o_i)
        parts_lse.append(lse_i)

    return merge_partials(jnp.stack(parts_o), jnp.stack(parts_lse))


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
    return bitdecode_attention_ref(
        q, kw, ks, kz, vw, vs, vz, k_res, v_res, pack_blocks, res_len,
        bits=bits, block_n=block_n, sm_scale=sm_scale, k_gran=k_gran,
        shared_kv=shared_kv, d_v=d_v, num_splits=num_splits,
        draft_bits=draft_bits,
    )
