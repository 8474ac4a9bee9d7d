"""Pallas TPU kernel: fused low-bit flash-decode attention (Packing Kernel),
with FlashDecoding-style split-KV sequence parallelism.

Two-phase reduction
-------------------
Phase 1 — grid = (B, H_kv, num_splits, bps + 1), bps = ceil(nb / num_splits):
each split owns a contiguous range of ``bps`` packed KV blocks and walks them
with online-softmax carries in VMEM scratch; the final grid step of the LAST
split additionally processes the half-precision *residual* buffer (paper
§IV-A(2)).  Every split finalizes into its own slot of the partials outputs
``o[num_splits, B, H, g, d_v]`` / ``lse[num_splits, B, H, g]`` — the first
three grid dimensions are independent ("parallel"), so a single-batch
long-context decode exposes ``B x H_kv x num_splits``-way parallelism instead
of the ``B x H_kv`` of the unsplit kernel (the FlashDecoding-v2 trick the
paper benchmarks against).

Phase 2 — :func:`merge_partials`, a small XLA epilogue: a logsumexp-weighted
combine of the per-split partials.  A split whose block range is entirely
beyond ``pack_blocks[b]`` never updates its carries, so ``finalize``'s l=0
guard emits lse ~ -inf and the merge weights it out *exactly* (the same
contract tests/test_splitkv_math.py pins for the cross-chip merge in
repro.dist.splitkv, which reuses this math over a mesh axis).

Cooperative-unit mapping (paper §III-A):
  * unpack + dequant: shift/mask/FMA on the VPU — the CUDA-core role;
  * QK^T and PV: `lax.dot_general` with bf16 operands, f32 accumulation on
    the MXU — the Tensor-Core role;
  * Mosaic's grid pipeline double-buffers the HBM→VMEM DMA of block i+1
    against the compute of block i — the paper's cp.async/wgmma software
    pipeline (§V-C(2)) falls out of the BlockSpec machinery;
  * the online-softmax carry in VMEM scratch across sequential grid steps
    replaces the multi-warp cooperative softmax (§IV-B(2)); the split axis
    replaces FlashDecoding's inter-CTA partials+combine.

The strided packed layout (core/layout.py) makes the unpack a handful of
full-width vector ops whose output is already in natural token order inside
the (sublane, lane) tile — the ldmatrix-induced-layout analogue.

`shared_kv=True` is the MLA latent-cache mode (DeepSeek): the cache holds a
single quantized latent stream; V is a channel-slice of the dequantized K
tile, so the latent is unpacked once and feeds both matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import layout

MASK_VALUE = -1e37

# Packed blocks whose metadata rows one grid step fetches: the TPU tiles the
# last two block dims, and a single row (1, d) of a [.., nb, d] parameter
# array is no legal tile.  16 rows is one bf16 (16, 128) tile.
META_ROWS = 16


def _unpack(w, bits):
    """int32 (npr, d) -> int32 (block_n, d), natural token order (strided layout)."""
    shifts, mask = layout.plane_shift_mask(bits)
    planes = [(w >> s) & mask for s in shifts]
    return jnp.concatenate(planes, axis=0)


def make_flash_update(q, m_scr, l_scr, acc_scr, sm_scale):
    """Online-softmax update closure shared by the dense and paged kernels.
    q: (g, d_k) bf16; scratch refs hold the running (m, l, acc) carries."""

    def update(k_tile, v_tile, row_mask=None):
        s = (
            lax.dot_general(
                q, k_tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )  # (g, n) — MXU
        if row_mask is not None:
            s = jnp.where(row_mask, s, MASK_VALUE)
        m_prev = m_scr[...]  # (g, 128) lane-replicated
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (g, 1)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])  # (g, n)
        l_next = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(
            p.astype(jnp.bfloat16), v_tile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (g, d_v) — MXU
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv
        m_scr[...] = m_next
        l_scr[...] = l_next

    return update


def dequant_tile(wq, scale, zero, k_gran):
    """(n, d) int codes + params -> bf16 tile (VPU scale-FMA)."""
    s = scale.astype(jnp.float32)
    z = zero.astype(jnp.float32)
    if k_gran == "channel":  # params per channel: (d,)
        return (wq.astype(jnp.float32) * s[None, :] + z[None, :]).astype(jnp.bfloat16)
    return (wq.astype(jnp.float32) * s[:, None] + z[:, None]).astype(jnp.bfloat16)


def finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    """Write the carries' normalized output and logsumexp.  The carries are
    ``(g, .)`` (one head) or ``(H, g, .)`` (all heads of a step); ``lse_ref``
    keeps a unit lane axis so its block stays a legal TPU tile."""
    # guard l=0 (all tokens masked — e.g. a split whose block range lies
    # beyond pack_blocks, or an empty split-KV shard): output zeros with
    # lse ~ -inf so merge_partials / the cross-chip merge weights it out
    # exactly
    l = jnp.maximum(l_scr[...], 1e-30)
    o = acc_scr[...] / l[..., :1]
    o_ref[...] = o.reshape(o_ref.shape).astype(o_ref.dtype)
    lse = m_scr[..., :1] + jnp.log(l[..., :1])
    lse_ref[...] = lse.reshape(lse_ref.shape)


def init_carries(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full(m_scr.shape, MASK_VALUE, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)


def merge_partials(o_parts, lse_parts, *, return_lse: bool = True):
    """Phase-2 combine of per-split flash partials (XLA epilogue).

    o_parts: [S, ..., g, d_v] per-split normalized outputs;
    lse_parts: [S, ..., g] per-split logsumexps.  Splits with no valid
    tokens carry lse ~ -inf (finalize's l=0 guard) and get weight exp(-inf)=0,
    so empty splits drop out exactly — the same lse-merge the distributed
    layer (repro.dist.splitkv) runs across a mesh axis, specified by
    tests/test_splitkv_math.py.
    """
    m = jnp.max(lse_parts, axis=0)
    w = jnp.exp(lse_parts - m[None])  # [S, ..., g]
    den = jnp.maximum(jnp.sum(w, axis=0), 1e-30)
    num = jnp.sum(w[..., None] * o_parts, axis=0)
    out = num / den[..., None]
    if not return_lse:
        return out
    return out, m + jnp.log(den)


def _pick_row(tile_ref, r):
    """Row ``r`` (traced) of a ``(1, 1, rows, d)`` metadata block, as f32
    ``(d,)``: a masked sum over the rows, with no dynamic sublane slice."""
    tile = tile_ref[0, 0].astype(jnp.float32)
    rows = lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.sum(jnp.where(rows == r, tile, 0.0), axis=0)


def _body(
    pb_ref,
    rl_ref,
    q_ref,
    kw_ref,
    ks_ref,
    kz_ref,
    vw_ref,
    vs_ref,
    vz_ref,
    kres_ref,
    vres_ref,
    o_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    bits,
    block_n,
    bps,
    num_splits,
    res_n,
    sm_scale,
    k_gran,
    shared_kv,
    d_v,
    meta_rows,
):
    b = pl.program_id(0)
    s = pl.program_id(2)
    j = pl.program_id(3)
    jj = s * bps + j  # global packed-block index owned by this grid step

    @pl.when(j == 0)
    def _init():
        init_carries(m_scr, l_scr, acc_scr)

    q = q_ref[0, 0].astype(jnp.bfloat16)  # (g, d_k)
    update = make_flash_update(q, m_scr, l_scr, acc_scr, sm_scale)

    @pl.when(jnp.logical_and(j < bps, jj < pb_ref[b]))
    def _packed_block():
        kw = kw_ref[0, 0, 0]  # (npr, d_k) int32
        kq = _unpack(kw, bits)  # (block_n, d_k) — VPU
        r = jj % meta_rows  # row of this block within the metadata tile
        k_hat = dequant_tile(kq, _pick_row(ks_ref, r), _pick_row(kz_ref, r),
                             k_gran)
        if shared_kv:
            v_hat = k_hat[:, :d_v]
        else:
            vq = _unpack(vw_ref[0, 0, 0], bits)
            v_hat = dequant_tile(vq, _pick_row(vs_ref, r), _pick_row(vz_ref, r),
                                 "tensor")
        update(k_hat, v_hat)

    # residual tail belongs to the LAST split only; every split finalizes
    # its own partials slot at its last grid step
    @pl.when(jnp.logical_and(j == bps, s == num_splits - 1))
    def _residual():
        kr = kres_ref[0, 0].astype(jnp.bfloat16)  # (res_n, d_k)
        if shared_kv:
            vr = kres_ref[0, 0, :, :d_v].astype(jnp.bfloat16)
        else:
            vr = vres_ref[0, 0].astype(jnp.bfloat16)
        mask = lax.broadcasted_iota(jnp.int32, (1, res_n), 1) < rl_ref[b]
        update(kr, vr, row_mask=mask)

    @pl.when(j == bps)
    def _finalize():
        finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _kernel_standard(pb, rl, q, kw, ks, kz, vw, vs, vz, kres, vres,
                     o, lse, m, l, acc, **kwargs):
    _body(pb, rl, q, kw, ks, kz, vw, vs, vz, kres, vres, o, lse, m, l, acc, **kwargs)


def _kernel_shared(pb, rl, q, kw, ks, kz, kres, o, lse, m, l, acc, **kwargs):
    _body(pb, rl, q, kw, ks, kz, None, None, None, kres, None, o, lse, m, l, acc,
          **kwargs)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bits", "block_n", "sm_scale", "k_gran", "shared_kv", "d_v",
        "num_splits", "interpret",
    ),
)
def bitdecode_attention_pallas(
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
    block_n: int,
    sm_scale: float,
    k_gran: str,
    shared_kv: bool,
    d_v: int,
    num_splits: int = 1,
    interpret: bool,
):
    """Inputs must be pre-padded: g % 8 == 0, d_k % 128 == 0, d_v % 128 == 0.

    Returns per-split partials (o [S,B,H,g,d_v] f32, lse [S,B,H,g] f32) with
    S = num_splits; combine with :func:`merge_partials` (exact for S = 1).
    """
    b, h, g, d_k = q.shape
    nb, npr = kw.shape[2], kw.shape[3]
    res_n = k_res.shape[2]
    num_splits = max(1, min(num_splits, nb))
    bps = -(-nb // num_splits)  # packed blocks per split
    n_steps = bps + 1

    def blk(s, j):
        # block fetched at step (s, j); clamped so the residual/tail steps
        # DMA an in-range (ignored) block
        return jnp.minimum(s * bps + j, nb - 1)

    # metadata moves in tiles of meta_rows blocks (refetched only when the
    # walk crosses a tile); pad the block axis to whole tiles
    meta_rows = nb if nb <= META_ROWS else META_ROWS
    nb_meta = -(-nb // meta_rows) * meta_rows

    def meta(x):
        if x is None or nb_meta == nb:
            return x
        return jnp.pad(x, ((0, 0), (0, 0), (0, nb_meta - nb), (0, 0)))

    k_scale, k_zero, v_scale, v_zero = map(meta, (k_scale, k_zero, v_scale, v_zero))

    q_spec = pl.BlockSpec((1, 1, g, d_k), lambda i, hh, s, j, *_: (i, hh, 0, 0))
    kw_spec = pl.BlockSpec(
        (1, 1, 1, npr, d_k), lambda i, hh, s, j, *_: (i, hh, blk(s, j), 0, 0)
    )
    kp_shape = (1, 1, meta_rows, d_k if k_gran == "channel" else block_n)
    kp_spec = pl.BlockSpec(
        kp_shape, lambda i, hh, s, j, *_: (i, hh, blk(s, j) // meta_rows, 0))
    kres_spec = pl.BlockSpec((1, 1, res_n, d_k), lambda i, hh, s, j, *_: (i, hh, 0, 0))

    in_specs = [q_spec, kw_spec, kp_spec, kp_spec]
    operands = [q, kw, k_scale, k_zero]
    if not shared_kv:
        vw_spec = pl.BlockSpec(
            (1, 1, 1, npr, d_v), lambda i, hh, s, j, *_: (i, hh, blk(s, j), 0, 0)
        )
        vp_spec = pl.BlockSpec(
            (1, 1, meta_rows, block_n),
            lambda i, hh, s, j, *_: (i, hh, blk(s, j) // meta_rows, 0),
        )
        vres_spec = pl.BlockSpec(
            (1, 1, res_n, d_v), lambda i, hh, s, j, *_: (i, hh, 0, 0)
        )
        in_specs += [vw_spec, vp_spec, vp_spec, kres_spec, vres_spec]
        operands += [vw, v_scale, v_zero, k_res, v_res]
        kernel = _kernel_standard
    else:
        in_specs += [kres_spec]
        operands += [k_res]
        kernel = _kernel_shared

    out_specs = [
        pl.BlockSpec((1, 1, 1, g, d_v), lambda i, hh, s, j, *_: (s, i, hh, 0, 0)),
        pl.BlockSpec((1, 1, 1, g, 1), lambda i, hh, s, j, *_: (s, i, hh, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((num_splits, b, h, g, d_v), jnp.float32),
        jax.ShapeDtypeStruct((num_splits, b, h, g, 1), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((g, 128), jnp.float32),
        pltpu.VMEM((g, 128), jnp.float32),
        pltpu.VMEM((g, d_v), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, num_splits, n_steps),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    body = functools.partial(
        kernel,
        bits=bits,
        block_n=block_n,
        bps=bps,
        num_splits=num_splits,
        res_n=res_n,
        sm_scale=sm_scale,
        k_gran=k_gran,
        shared_kv=shared_kv,
        d_v=d_v,
        meta_rows=meta_rows,
    )
    out, lse = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="bitdecode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
    )(pack_blocks.astype(jnp.int32), res_len.astype(jnp.int32), *operands)
    return out, lse[..., 0]
