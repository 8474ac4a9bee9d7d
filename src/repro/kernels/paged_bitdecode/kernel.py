"""Pallas TPU kernel: paged low-bit flash-decode attention (paper's Page
setting, §VI-A), with the same split-KV grid as kernels/bitdecode.

TPU-idiomatic paging: instead of a scalar-core page-table walk (vLLM/GPU),
the page table is a *scalar-prefetch* operand — BlockSpec index_maps read
``page_table[b, jj]`` to pick which page of the global pool the next grid
step's DMA fetches, so page indirection rides the same double-buffered
HBM→VMEM pipeline as the dense kernel (zero extra kernels, zero gathers).

Split-KV: grid = (B, num_splits, bps + 1); split ``s`` walks page-table
entries [s*bps, (s+1)*bps), writes its own slot of the per-split partials
(o [S,B,H,g,d_v], lse [S,B,H,g]); the residual tail rides with the last
split and the partials are combined by the shared logsumexp merge epilogue
(bitdecode.kernel.merge_partials).

Pools are [n_pages, H, ...] and one grid step moves one page of *every*
KV head (blocks ``(1, H, ...)``), running the heads' flash updates one after
another in registers; everything else matches kernels/bitdecode.

``shared_kv=True`` is the MLA latent-cache mode, mirrored from the dense
kernel: the pools hold a single quantized latent stream, there are no V-side
pools at all, and the V tile is a channel slice (``[:, :d_v]``) of the
dequantized K tile — one pool page read per grid step feeds both matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax import lax

from repro.kernels.bitdecode.kernel import (_unpack, dequant_tile, finalize,
                                            init_carries, make_flash_update)


def _paged_body(pt_ref, pb_ref, rl_ref, q_ref, kw_ref, ks_ref, kz_ref,
                vw_ref, vs_ref, vz_ref, kres_ref, vres_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, bits, bps, num_splits, res_n, sm_scale, k_gran,
                shared_kv, d_v):
    b = pl.program_id(0)
    s = pl.program_id(1)
    j = pl.program_id(2)
    jj = s * bps + j  # global page-table slot owned by this grid step
    n_heads = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        init_carries(m_scr, l_scr, acc_scr)

    updates = [
        make_flash_update(q_ref[0, hh].astype(jnp.bfloat16), m_scr.at[hh],
                          l_scr.at[hh], acc_scr.at[hh], sm_scale)
        for hh in range(n_heads)
    ]

    @pl.when(jnp.logical_and(j < bps, jj < pb_ref[b]))
    def _packed_page():
        # one page of every KV head: the metadata blocks hold all heads'
        # rows, picked per head in registers
        ks = ks_ref[0].astype(jnp.float32)
        kz = kz_ref[0].astype(jnp.float32)
        if not shared_kv:
            vs = vs_ref[0].astype(jnp.float32)
            vz = vz_ref[0].astype(jnp.float32)
        for hh in range(n_heads):
            kq = _unpack(kw_ref[0, hh], bits)
            k_hat = dequant_tile(kq, ks[hh], kz[hh], k_gran)
            if shared_kv:
                v_hat = k_hat[:, :d_v]
            else:
                vq = _unpack(vw_ref[0, hh], bits)
                v_hat = dequant_tile(vq, vs[hh], vz[hh], "tensor")
            updates[hh](k_hat, v_hat)

    @pl.when(jnp.logical_and(j == bps, s == num_splits - 1))
    def _residual():
        mask = lax.broadcasted_iota(jnp.int32, (1, res_n), 1) < rl_ref[b]
        for hh in range(n_heads):
            kr = kres_ref[0, hh].astype(jnp.bfloat16)
            if shared_kv:
                vr = kres_ref[0, hh, :, :d_v].astype(jnp.bfloat16)
            else:
                vr = vres_ref[0, hh].astype(jnp.bfloat16)
            updates[hh](kr, vr, row_mask=mask)

    @pl.when(j == bps)
    def _finalize():
        finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _kernel_standard(pt, pb, rl, q, kw, ks, kz, vw, vs, vz, kres, vres,
                     o, lse, m, l, acc, **kw_args):
    _paged_body(pt, pb, rl, q, kw, ks, kz, vw, vs, vz, kres, vres,
                o, lse, m, l, acc, **kw_args)


def _kernel_shared(pt, pb, rl, q, kw, ks, kz, kres, o, lse, m, l, acc,
                   **kw_args):
    _paged_body(pt, pb, rl, q, kw, ks, kz, None, None, None, kres, None,
                o, lse, m, l, acc, **kw_args)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block_n", "sm_scale", "k_gran", "shared_kv",
                     "d_v", "num_splits", "interpret"),
)
def paged_bitdecode_attention_pallas(
    q,             # [B, H, g, d_k]  (pre-padded)
    kw_pool,       # int32 [P, H, npr, d_k]
    k_scale_pool,  # [P, H, d_k] (channel) or [P, H, block_n]
    k_zero_pool,
    vw_pool,       # int32 [P, H, npr, d_v]; None when shared_kv
    v_scale_pool,  # [P, H, block_n]; None when shared_kv
    v_zero_pool,
    k_res, v_res,  # [B, H, res_n, d]; v_res None when shared_kv
    page_table,    # int32 [B, nb_max]
    pack_blocks, res_len,
    *,
    bits: int, block_n: int, sm_scale: float, k_gran: str,
    shared_kv: bool = False, d_v: int | None = None,
    num_splits: int = 1, interpret: bool,
):
    """Returns per-split partials (o [S,B,H,g,d_v], lse [S,B,H,g])."""
    b, h, g, d_k = q.shape
    _, _, npr, _ = kw_pool.shape
    if not shared_kv:
        d_v = vw_pool.shape[-1]
    nb = page_table.shape[1]
    res_n = k_res.shape[2]
    num_splits = max(1, min(num_splits, nb))
    bps = -(-nb // num_splits)
    n_steps = bps + 1

    def page(s, j, pt_ref, b_):
        # page id for grid step (s, j) of sequence b (clamped for the
        # residual/tail steps so the prefetch DMA stays in range)
        return pt_ref[b_, jnp.minimum(s * bps + j, nb - 1)]

    # every block spans all H heads of a page: the TPU tiles the last two
    # block dims, and a metadata row of one head ((1, 1, d) over [P, H, d])
    # is not a legal tile, while (1, H, d) covers the head axis in full
    def pool_spec(*minor):
        return pl.BlockSpec(
            (1, h, *minor),
            lambda i, s, j, pt, pb, rl: (page(s, j, pt, i), 0) + (0,) * len(minor),
        )

    def seq_spec(*minor):
        return pl.BlockSpec((1, h, *minor), lambda i, s, j, *_: (i, 0, 0, 0))

    kp_last = d_k if k_gran == "channel" else block_n
    in_specs = [seq_spec(g, d_k), pool_spec(npr, d_k), pool_spec(kp_last),
                pool_spec(kp_last)]
    operands = [q, kw_pool, k_scale_pool, k_zero_pool]
    if not shared_kv:
        in_specs += [pool_spec(npr, d_v), pool_spec(block_n), pool_spec(block_n),
                     seq_spec(res_n, d_k), seq_spec(res_n, d_v)]
        operands += [vw_pool, v_scale_pool, v_zero_pool, k_res, v_res]
        kernel = _kernel_standard
    else:
        in_specs += [seq_spec(res_n, d_k)]
        operands += [k_res]
        kernel = _kernel_shared

    out_specs = [
        pl.BlockSpec((1, 1, h, g, d_v), lambda i, s, j, *_: (s, i, 0, 0, 0)),
        pl.BlockSpec((1, 1, h, g, 1), lambda i, s, j, *_: (s, i, 0, 0, 0)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, num_splits, n_steps),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((h, g, 128), jnp.float32),
            pltpu.VMEM((h, g, 128), jnp.float32),
            pltpu.VMEM((h, g, d_v), jnp.float32),
        ],
    )
    body = functools.partial(
        kernel, bits=bits, bps=bps, num_splits=num_splits, res_n=res_n,
        sm_scale=sm_scale, k_gran=k_gran, shared_kv=shared_kv, d_v=d_v,
    )
    out, lse = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((num_splits, b, h, g, d_v), jnp.float32),
            jax.ShapeDtypeStruct((num_splits, b, h, g, 1), jnp.float32),
        ],
        interpret=interpret,
        name="paged_bitdecode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(page_table.astype(jnp.int32), pack_blocks.astype(jnp.int32),
      res_len.astype(jnp.int32), *operands)
    return out, lse[..., 0]
