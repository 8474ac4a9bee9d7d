"""Pallas TPU kernel: paged low-bit flash-decode attention (paper's Page
setting, §VI-A), with FlashDecoding-style split-KV sequence parallelism.

TPU-idiomatic paging: instead of a scalar-core page-table walk (vLLM/GPU),
the page table is a *scalar-prefetch* operand — BlockSpec index_maps read
``page_table[b, jj]`` to pick which page of the global pool the next grid
step's DMA fetches, so page indirection rides the double-buffered HBM→VMEM
pipeline (zero extra kernels, zero gathers).

Two-phase reduction
-------------------
Phase 1 — grid = (B, num_splits, bps + 1), bps = ceil(nb / num_splits):
split ``s`` walks page-table entries [s*bps, (s+1)*bps) with online-softmax
carries in VMEM scratch; the final grid step of the LAST split additionally
processes the half-precision *residual* buffer (paper §IV-A(2)).  Every
split finalizes into its own slot of the partials outputs
(o [S,B,H,g,d_v], lse [S,B,H,g]), so a single-batch long-context decode
exposes ``B x num_splits``-way grid parallelism.

Phase 2 — :func:`merge_partials`, a small XLA epilogue: a logsumexp-weighted
combine of the per-split partials.  A split whose range lies entirely
beyond ``pack_blocks[b]`` never updates its carries, so ``finalize``'s l=0
guard emits lse ~ -inf and the merge weights it out *exactly* (the same
contract tests/test_splitkv_math.py pins for the cross-chip merge in
repro.dist.splitkv, which reuses this math over a mesh axis).

Cooperative-unit mapping (paper §III-A):
  * unpack + dequant: shift/mask/FMA on the VPU — the CUDA-core role;
  * QK^T and PV: `lax.dot_general` with bf16 operands, f32 accumulation on
    the MXU — the Tensor-Core role;
  * Mosaic's grid pipeline double-buffers the HBM→VMEM DMA of page i+1
    against the compute of page i — the paper's cp.async/wgmma software
    pipeline (§V-C(2)) falls out of the BlockSpec machinery;
  * the online-softmax carry in VMEM scratch across sequential grid steps
    replaces the multi-warp cooperative softmax (§IV-B(2)); the split axis
    replaces FlashDecoding's inter-CTA partials+combine.

The strided packed layout (core/layout.py) makes the unpack a handful of
full-width vector ops whose output is already in natural token order inside
the (sublane, lane) tile — the ldmatrix-induced-layout analogue.

Pools are layer-stacked, [L, n_pages, H, ...], and a scalar-prefetch
``layer`` picks the layer the walk reads: the decode layer scan hands the
kernel the whole stack and never slices one layer's pool out of it.  One
grid step moves one page of *every* KV head of that layer (blocks
``(1, H, ...)``, the layer dim squeezed), running the heads' flash updates
one after another in registers.

``shared_kv=True`` is the MLA latent-cache mode: the pools hold a single
quantized latent stream, there are no V-side pools at all, and the V tile
is a channel slice (``[:, :d_v]``) of the dequantized K tile — one pool
page read per grid step feeds both matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax import lax

from repro.core import layout

MASK_VALUE = -1e37


def _unpack(w, bits):
    """int32 (npr, d) -> int32 (block_n, d), natural token order (strided layout)."""
    shifts, mask = layout.plane_shift_mask(bits)
    planes = [(w >> s) & mask for s in shifts]
    return jnp.concatenate(planes, axis=0)


def make_flash_update(q, m_scr, l_scr, acc_scr, sm_scale):
    """Online-softmax update closure for one KV head.
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
    ``(H, g, .)`` (all heads of a step); ``lse_ref`` keeps a unit lane axis
    so its block stays a legal TPU tile."""
    # guard l=0 (all tokens masked — e.g. a split whose range lies beyond
    # pack_blocks, or an empty split-KV shard): output zeros with lse ~ -inf
    # so merge_partials / the cross-chip merge weights it out exactly
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


def _paged_body(pt_ref, pb_ref, rl_ref, ly_ref, q_ref, kw_ref, ks_ref, kz_ref,
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


def _kernel_standard(pt, pb, rl, ly, q, kw, ks, kz, vw, vs, vz, kres, vres,
                     o, lse, m, l, acc, **kw_args):
    _paged_body(pt, pb, rl, ly, q, kw, ks, kz, vw, vs, vz, kres, vres,
                o, lse, m, l, acc, **kw_args)


def _kernel_shared(pt, pb, rl, ly, q, kw, ks, kz, kres, o, lse, m, l, acc,
                   **kw_args):
    _paged_body(pt, pb, rl, ly, q, kw, ks, kz, None, None, None, kres, None,
                o, lse, m, l, acc, **kw_args)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block_n", "sm_scale", "k_gran", "shared_kv",
                     "d_v", "num_splits", "interpret"),
)
def paged_bitdecode_attention_pallas(
    q,             # [B, H, g, d_k]  (pre-padded)
    kw_pool,       # int32 [L, P, H, npr, d_k]
    k_scale_pool,  # [L, P, H, d_k] (channel) or [L, P, H, block_n]
    k_zero_pool,
    vw_pool,       # int32 [L, P, H, npr, d_v]; None when shared_kv
    v_scale_pool,  # [L, P, H, block_n]; None when shared_kv
    v_zero_pool,
    k_res, v_res,  # [B, H, res_n, d]; v_res None when shared_kv
    page_table,    # int32 [B, nb_max]
    pack_blocks, res_len,
    layer,         # int32 scalar: the layer of the pools the walk reads
    *,
    bits: int, block_n: int, sm_scale: float, k_gran: str,
    shared_kv: bool = False, d_v: int | None = None,
    num_splits: int = 1, interpret: bool,
):
    """Returns per-split partials (o [S,B,H,g,d_v], lse [S,B,H,g])."""
    b, h, g, d_k = q.shape
    npr = kw_pool.shape[-2]
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
            (pl.squeezed, 1, h, *minor),
            lambda i, s, j, pt, pb, rl, ly: (ly[0], page(s, j, pt, i), 0)
            + (0,) * len(minor),
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
        num_scalar_prefetch=4,
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
      res_len.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      *operands)
    return out, lse[..., 0]
