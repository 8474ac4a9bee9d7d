"""Pallas TPU kernel: paged low-bit flash-decode attention (paper's Page
setting, §VI-A), with FlashDecoding-style split-KV sequence parallelism.

Paging: the page table, ``pack_blocks``, ``res_len`` and ``layer`` are
*scalar-prefetch* operands in SMEM, and the page pools stay in HBM.  Each
grid step owns one row and one split, and walks that row's own pages in an
in-kernel loop bounded by its ``pack_blocks``: the scalar core reads
``page_table[b, jj]`` and issues one DMA per pool block of that page into
VMEM.  A table column at or past ``pack_blocks[b]`` costs nothing: no grid
step, no DMA, no compute.

Two-phase reduction
-------------------
Phase 1 — grid = (B, num_splits), bps = ceil(nb / num_splits): split ``s``
of row ``b`` walks columns [s*bps, min((s+1)*bps, pack_blocks[b])) with
online-softmax carries in VMEM scratch; the LAST split then processes the
half-precision *residual* buffer (paper §IV-A(2)).  Every split finalizes
into its own slot of the partials outputs (o [S,B,H,g,d_v], lse [S,B,H,g]),
so a single-batch long-context decode exposes ``B x num_splits``-way grid
parallelism.

Phase 2 — :func:`merge_partials`, a small XLA epilogue: a logsumexp-weighted
combine of the per-split partials.  A split whose range lies entirely
beyond ``pack_blocks[b]`` never updates its carries, so ``finalize``'s l=0
guard emits lse ~ -inf and the merge weights it out *exactly* (the same
contract tests/test_splitkv_math.py pins for the cross-chip merge in
repro.dist.splitkv, which reuses this math over a mesh axis).

The walk moves pages in chunks of P (:func:`chunk_pages`: as many pages as
fit ``_CHUNK_BYTES``, from the bytes of one page, which the shapes give)
between two VMEM slots: while chunk c computes, chunk c+1's DMAs are in
flight; each page has its own DMA semaphore, so page p of a chunk computes
as soon as its own blocks have landed.

Cooperative-unit mapping (paper §III-A):
  * unpack + dequant: shift/mask/FMA on the VPU — the CUDA-core role;
  * QK^T and PV: `lax.dot_general` with bf16 operands, f32 accumulation on
    the MXU — the Tensor-Core role;
  * the chunked, double-buffered page DMAs against the compute of the
    chunk before — the paper's cp.async/wgmma software pipeline (§V-C(2));
  * the online-softmax carry in VMEM scratch across the pages of the loop
    replaces the multi-warp cooperative softmax (§IV-B(2)); the split axis
    replaces FlashDecoding's inter-CTA partials+combine.

The strided packed layout (core/layout.py) makes the unpack a handful of
full-width vector ops whose output is already in natural token order inside
the (sublane, lane) tile — the ldmatrix-induced-layout analogue.

Pools are layer-stacked, [L, n_pages, H, ...], and the scalar ``layer``
picks the layer the walk reads: the decode layer scan hands the kernel the
whole stack and never slices one layer's pool out of it.  One page moves
the blocks of *every* KV head of that layer (``(H, ...)``), and the heads'
flash updates run one after another.  A pool whose page block is no legal
DMA slice (one head's bf16 scale or zero: the TPU packs 16-bit rows in
pairs) is instead read whole for the walk's layer, once per call, and
indexed by page in VMEM.

``shared_kv=True`` is the MLA latent-cache mode: the pools hold a single
quantized latent stream, there are no V-side pools at all, and the V tile
is a channel slice (``[:, :d_v]``) of the dequantized K tile — one pool
page read feeds both matmuls.
"""
from __future__ import annotations

import functools
import math

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


# Bytes of page blocks one chunk of the walk fetches: a chunk's DMAs are in
# flight together while the chunk before it computes.
_CHUNK_BYTES = 512 * 1024
_MAX_CHUNK_PAGES = 8


def chunk_pages(pools, bps: int) -> int:
    """Pages per DMA chunk of the walk over ``pools`` ([L, P, ...] stacks):
    as many pages of the pools it copies page by page as fit
    ``_CHUNK_BYTES`` (at least one, at most ``_MAX_CHUNK_PAGES``), and no
    more than a split walks (``bps``)."""
    page_bytes = sum(math.prod(p.shape[2:]) * p.dtype.itemsize
                     for p in pools if _page_dma_legal(p))
    return max(1, min(_MAX_CHUNK_PAGES, _CHUNK_BYTES // page_bytes, bps))


def _page_dma_legal(pool) -> bool:
    """Whether one page's block of ``pool`` ([L, P, rows, lanes]) can be
    copied on its own.  The TPU packs 16-bit rows in pairs, so an odd count
    of bf16 rows (one head's scale or zero) is no legal DMA slice; such a
    pool is read whole for the walk's layer instead."""
    return pool.shape[-2] * pool.dtype.itemsize % 4 == 0


def _paged_body(pt_ref, pb_ref, rl_ref, ly_ref, q_ref, kres_ref, vres_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, sem, pools, bufs,
                *, bits, bps, chunk, num_splits, res_n, sm_scale, k_gran,
                shared_kv, d_v):
    """``pools``: per pool, its HBM stack (copied page by page into the
    VMEM chunk buffers ``bufs``) or, where ``bufs`` holds None, the walk
    layer's whole pool in VMEM."""
    b = pl.program_id(0)
    s = pl.program_id(1)
    lo = s * bps  # first page-table column of this split
    n_pages = jnp.clip(pb_ref[b] - lo, 0, bps)  # the row's own pages here
    n_chunks = (n_pages + chunk - 1) // chunk
    n_heads = q_ref.shape[1]

    init_carries(m_scr, l_scr, acc_scr)

    def head_update(hh):
        return make_flash_update(q_ref[0, hh].astype(jnp.bfloat16), m_scr.at[hh],
                                 l_scr.at[hh], acc_scr.at[hh], sm_scale)

    def in_chunk(c):  # pages of chunk c (<= 0 past the last chunk)
        return jnp.minimum(chunk, n_pages - c * chunk)

    def page_id(c, p):
        return pt_ref[b, lo + c * chunk + p]

    def page_copies(c, slot, p):
        # page p of chunk c, every KV head of the walk's layer, into the
        # VMEM buffers of ``slot``; one semaphore per (slot, page)
        page = page_id(c, p)
        return [pltpu.make_async_copy(src.at[ly_ref[0], page], dst.at[slot, p],
                                      sem.at[slot, p])
                for src, dst in zip(pools, bufs) if dst is not None]

    def start_chunk(c, slot):
        def start(p, carry):
            for cp in page_copies(c, slot, p):
                cp.start()
            return carry

        lax.fori_loop(0, in_chunk(c), start, 0)

    def page_update(c, slot, p):
        # one page of every KV head: the metadata blocks hold all heads'
        # rows, picked per head in registers
        page = page_id(c, p)
        kw, ks, kz, *v_side = [src.at[page] if dst is None else dst.at[slot, p]
                               for src, dst in zip(pools, bufs)]
        ks = ks[...].astype(jnp.float32)
        kz = kz[...].astype(jnp.float32)
        if not shared_kv:
            vw, vs, vz = v_side
            vs = vs[...].astype(jnp.float32)
            vz = vz[...].astype(jnp.float32)
        for hh in range(n_heads):
            kq = _unpack(kw[hh], bits)
            k_hat = dequant_tile(kq, ks[hh], kz[hh], k_gran)
            if shared_kv:
                v_hat = k_hat[:, :d_v]
            else:
                vq = _unpack(vw[hh], bits)
                v_hat = dequant_tile(vq, vs[hh], vz[hh], "tensor")
            head_update(hh)(k_hat, v_hat)

    def run_chunk(c, carry):
        slot = lax.rem(c, 2)
        start_chunk(c + 1, 1 - slot)  # in flight while chunk c computes

        def page(p, carry):
            for cp in page_copies(c, slot, p):
                cp.wait()
            page_update(c, slot, p)
            return carry

        lax.fori_loop(0, in_chunk(c), page, 0)
        return carry

    start_chunk(0, 0)
    lax.fori_loop(0, n_chunks, run_chunk, 0)

    @pl.when(s == num_splits - 1)
    def _residual():
        mask = lax.broadcasted_iota(jnp.int32, (1, res_n), 1) < rl_ref[b]
        for hh in range(n_heads):
            kr = kres_ref[0, hh].astype(jnp.bfloat16)
            if shared_kv:
                vr = kres_ref[0, hh, :, :d_v].astype(jnp.bfloat16)
            else:
                vr = vres_ref[0, hh].astype(jnp.bfloat16)
            head_update(hh)(kr, vr, row_mask=mask)

    finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _kernel(pt, pb, rl, ly, *refs, shared_kv, streamed, **kw_args):
    """Unpacks the refs: q, k_res[, v_res], the pools; o, lse; the carries,
    a chunk buffer per streamed pool, the semaphores."""
    n_seq = 2 if shared_kv else 3
    n_pools = len(streamed)
    q, kres, vres = refs[0], refs[1], None if shared_kv else refs[2]
    pools = refs[n_seq:n_seq + n_pools]
    o, lse, m, l, acc = refs[n_seq + n_pools:n_seq + n_pools + 5]
    rest = iter(refs[n_seq + n_pools + 5:-1])
    bufs = [next(rest) if st else None for st in streamed]
    _paged_body(pt, pb, rl, ly, q, kres, vres, o, lse, m, l, acc, refs[-1],
                pools, bufs, shared_kv=shared_kv, **kw_args)


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
    pools = [kw_pool, k_scale_pool, k_zero_pool]
    if not shared_kv:
        pools += [vw_pool, v_scale_pool, v_zero_pool]
        d_v = vw_pool.shape[-1]
    nb = page_table.shape[1]
    res_n = k_res.shape[2]
    num_splits = max(1, min(num_splits, nb))
    bps = -(-nb // num_splits)
    streamed = tuple(_page_dma_legal(p) for p in pools)
    chunk = chunk_pages(pools, bps)

    def seq_spec(*minor):
        return pl.BlockSpec((1, h, *minor), lambda i, s, *_: (i, 0, 0, 0))

    def pool_spec(pool, st):
        if st:  # stays in HBM: the kernel copies each page it walks
            return pl.BlockSpec(memory_space=pltpu.HBM)
        # the walk layer's whole pool, fetched once per call
        return pl.BlockSpec(
            (pl.squeezed, *pool.shape[1:]),
            lambda i, s, pt, pb, rl, ly: (ly[0],) + (0,) * (pool.ndim - 1),
            pipeline_mode=pl.Buffered(1))

    in_specs = [seq_spec(g, d_k), seq_spec(res_n, d_k)]
    operands = [q, k_res]
    if not shared_kv:
        in_specs.append(seq_spec(res_n, d_v))
        operands.append(v_res)
    in_specs += [pool_spec(p, st) for p, st in zip(pools, streamed)]
    operands += pools

    out_specs = [
        pl.BlockSpec((1, 1, h, g, d_v), lambda i, s, *_: (s, i, 0, 0, 0)),
        pl.BlockSpec((1, 1, h, g, 1), lambda i, s, *_: (s, i, 0, 0, 0)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, num_splits),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((h, g, 128), jnp.float32),
            pltpu.VMEM((h, g, 128), jnp.float32),
            pltpu.VMEM((h, g, d_v), jnp.float32),
            # two slots of ``chunk`` pages of every streamed pool
            *(pltpu.VMEM((2, chunk, *p.shape[2:]), p.dtype)
              for p, st in zip(pools, streamed) if st),
            pltpu.SemaphoreType.DMA((2, chunk)),
        ],
    )
    body = functools.partial(
        _kernel, shared_kv=shared_kv, streamed=streamed, bits=bits, bps=bps,
        chunk=chunk, num_splits=num_splits, res_n=res_n, sm_scale=sm_scale,
        k_gran=k_gran, d_v=d_v,
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
            dimension_semantics=("parallel", "parallel")
        ),
    )(page_table.astype(jnp.int32), pack_blocks.astype(jnp.int32),
      res_len.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      *operands)
    return out, lse[..., 0]
