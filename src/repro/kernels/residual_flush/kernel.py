"""Pallas TPU kernel: fused residual flush — the paper's Residual Kernel
proper (§V-B), decode-time face.

``qcache.append_decode`` keeps the newest tokens in a bf16 residual buffer
and must, exactly once every ``block_n`` tokens, quantize that block and
commit it into the packed low-bit cache.  This kernel does the whole flush
in one pass per ``(batch, head)``:

  1. the residual tile is DMA'd HBM→VMEM once;
  2. min/max stats, scale/zero, round/clip and the strided bit-pack all run
     in registers (``kv_quant.kernel.quant_block_tile`` — the same code the
     prefill-time kernel uses, so flushed blocks are bitwise identical to
     prefill-quantized ones);
  3. the packed words + params are written *directly into the cache* via
     ``input_output_aliases``: the packed arrays are donated, the output
     BlockSpec index map reads the per-sequence destination block
     ``dest_block[b]`` from scalar prefetch, and only that one block of
     words is touched — no whole-cache copy.  A block's param row alone is
     no legal TPU tile, so the params move as the head's whole ``(nb, p)``
     table and the row is replaced by an in-register select.

Per-sequence gating: ``full[b]`` (scalar prefetch) marks sequences whose
residual just filled.  Programs for non-full sequences copy their (aliased)
input block back unchanged — a one-block VMEM round-trip, only ever paid
when *some other* sequence in the batch flushes, because the caller wraps
the whole kernel invocation in ``lax.cond(any(full), ...)`` and skips it
entirely on the per-token hot path.

Constraints (TPU, non-interpret): ``d % 128 == 0`` (the aliased cache cannot
be lane-padded in place — the kernel raises otherwise) and
``block_n % (32 // bits) == 0`` (layout invariant).

The paged variant (:func:`paged_residual_flush_pallas`) commits through a
page table instead, one grid step per sequence covering every KV head
(blocks ``(1, H, ...)``, whose params form a legal tile): the destination
is a *pool page* index (``dest_page[b]``, scalar prefetch) into the shared
``[P, H, ...]`` pools rather than a block of sequence ``b``'s own cache.  Same aliasing trick, one extra invariant: the
per-sequence destinations must be pairwise distinct, because two grid rows
writing the same pool page would race.  Callers guarantee it by routing
non-flushing sequences to a reserved per-slot scratch page (pages
``[0, B)`` of every pool — see serve/pages.py); flushing sequences always
own distinct allocated pages.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.kv_quant.kernel import quant_block_tile


def aliased_minor_dims(d_k, d_v, block_n, k_gran, shared_kv) -> list[int]:
    """Minor (lane) dims of every in-place aliased output: the packed words'
    head dims plus the block_n-wide rows of tensor-granularity params.  All
    must be 128-aligned on TPU (the aliased cache cannot be lane-padded in
    place); shared between the kernel's trace-time check and ops.py's 'auto'
    dispatch so the two never drift."""
    minor = [d_k] + ([block_n] if k_gran == "tensor" else [])
    if not shared_kv:
        minor += [d_v, block_n]
    return minor


def _body(
    full_ref,
    dest_ref,
    kres_ref,
    *refs,
    bits,
    k_gran,
    shared_kv,
    param_dtype,
):
    if shared_kv:
        (kw_in, ks_in, kz_in, kw_out, ks_out, kz_out) = refs
        vres_ref = vw_in = vs_in = vz_in = vw_out = vs_out = vz_out = None
    else:
        (vres_ref, kw_in, ks_in, kz_in, vw_in, vs_in, vz_in,
         kw_out, ks_out, kz_out, vw_out, vs_out, vz_out) = refs
    b = pl.program_id(0)
    full = full_ref[b] != 0
    nb = ks_in.shape[2]
    dest = jnp.minimum(dest_ref[b], nb - 1)

    def put_row(out_ref, in_ref, row):
        # replace row `dest` of the head's (nb, p) param table
        rows = lax.broadcasted_iota(jnp.int32, in_ref.shape[2:], 0)
        out_ref[0, 0] = jnp.where(rows == dest, row[None, :], in_ref[0, 0])

    @pl.when(full)
    def _flush():
        k = kres_ref[0, 0].astype(jnp.float32)  # (block_n, d_k)
        w, s, z = quant_block_tile(
            k, bits=bits, granularity=k_gran, param_dtype=param_dtype
        )
        kw_out[0, 0, 0] = w
        put_row(ks_out, ks_in, s)
        put_row(kz_out, kz_in, z)
        if not shared_kv:
            v = vres_ref[0, 0].astype(jnp.float32)
            wv, sv, zv = quant_block_tile(
                v, bits=bits, granularity="tensor", param_dtype=param_dtype
            )
            vw_out[0, 0, 0] = wv
            put_row(vs_out, vs_in, sv)
            put_row(vz_out, vz_in, zv)

    @pl.when(jnp.logical_not(full))
    def _keep():
        # the output VMEM block must be written every grid step (it is DMA'd
        # back over the aliased cache block); restore the fetched input
        kw_out[0, 0, 0] = kw_in[0, 0, 0]
        ks_out[...] = ks_in[...]
        kz_out[...] = kz_in[...]
        if not shared_kv:
            vw_out[0, 0, 0] = vw_in[0, 0, 0]
            vs_out[...] = vs_in[...]
            vz_out[...] = vz_in[...]


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block_n", "k_gran", "shared_kv", "interpret"),
)
def residual_flush_pallas(
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
    interpret: bool,
):
    """Commit ``k_res[b]``/``v_res[b]`` into packed block ``dest_block[b]``
    of every sequence with ``full[b] != 0``; other sequences' caches pass
    through untouched.  Returns the updated packed arrays
    ``(kw, k_scale, k_zero, vw, v_scale, v_zero)`` (None V-side when
    ``shared_kv``), aliased in place on TPU.
    """
    b, h, nb, npr, d_k = kw.shape
    param_dtype = k_scale.dtype
    if not interpret:
        minor = aliased_minor_dims(
            d_k, None if shared_kv else vw.shape[-1], block_n, k_gran, shared_kv
        )
        if any(m % 128 for m in minor):
            raise ValueError(
                "residual_flush_pallas writes the cache in place and cannot "
                f"lane-pad it: minor dims {minor} must all be multiples of "
                "128 on TPU — use impl='xla' for this shape"
            )

    def dst(i, j, full_ref, dest_ref):
        # clamp keeps the DMA in range; NB a flush at pack_blocks == nb (a
        # sequence decoded past capacity) saturates here and OVERWRITES
        # block nb-1 — the same saturation the oracle's dynamic_slice
        # applies.  Callers size nb from max_seq so this is unreachable.
        return jnp.minimum(dest_ref[i], nb - 1)

    w_spec = pl.BlockSpec(
        (1, 1, 1, npr, d_k), lambda i, j, f, dr: (i, j, dst(i, j, f, dr), 0, 0)
    )
    kp_shape = (1, 1, nb, d_k if k_gran == "channel" else block_n)
    kp_spec = pl.BlockSpec(kp_shape, lambda i, j, f, dr: (i, j, 0, 0))
    kres_spec = pl.BlockSpec((1, 1, block_n, d_k), lambda i, j, f, dr: (i, j, 0, 0))

    in_specs = [kres_spec]
    operands = [k_res]
    out_specs = [w_spec, kp_spec, kp_spec]
    out_shape = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (kw, k_scale, k_zero)]
    if not shared_kv:
        d_v = vw.shape[-1]
        vres_spec = pl.BlockSpec(
            (1, 1, block_n, d_v), lambda i, j, f, dr: (i, j, 0, 0)
        )
        vw_spec = pl.BlockSpec(
            (1, 1, 1, npr, d_v), lambda i, j, f, dr: (i, j, dst(i, j, f, dr), 0, 0)
        )
        vp_spec = pl.BlockSpec((1, 1, nb, block_n), lambda i, j, f, dr: (i, j, 0, 0))
        in_specs += [vres_spec]
        operands += [v_res]
        out_specs += [vw_spec, vp_spec, vp_spec]
        out_shape += [
            jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (vw, v_scale, v_zero)
        ]
        packed_in_specs = [w_spec, kp_spec, kp_spec, vw_spec, vp_spec, vp_spec]
        packed_operands = [kw, k_scale, k_zero, vw, v_scale, v_zero]
    else:
        packed_in_specs = [w_spec, kp_spec, kp_spec]
        packed_operands = [kw, k_scale, k_zero]
    in_specs += packed_in_specs
    operands += packed_operands

    # alias each packed input onto its output; indices count the two
    # scalar-prefetch operands (full, dest_block) and the residual inputs
    n_lead = 2 + (1 if shared_kv else 2)
    aliases = {n_lead + i: i for i in range(len(packed_operands))}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    body = functools.partial(
        _body,
        bits=bits,
        k_gran=k_gran,
        shared_kv=shared_kv,
        param_dtype=param_dtype,
    )
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="residual_flush",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
    )(full.astype(jnp.int32), dest_block.astype(jnp.int32), *operands)
    if shared_kv:
        kw, k_scale, k_zero = out
        return kw, k_scale, k_zero, None, None, None
    return tuple(out)


def _paged_body(
    full_ref,
    dest_ref,
    kres_ref,
    *refs,
    bits,
    k_gran,
    shared_kv,
    param_dtype,
):
    if shared_kv:
        (kw_in, ks_in, kz_in, kw_out, ks_out, kz_out) = refs
        vres_ref = vw_in = vs_in = vz_in = vw_out = vs_out = vz_out = None
    else:
        (vres_ref, kw_in, ks_in, kz_in, vw_in, vs_in, vz_in,
         kw_out, ks_out, kz_out, vw_out, vs_out, vz_out) = refs
    b = pl.program_id(0)
    full = full_ref[b] != 0

    @pl.when(full)
    def _flush():
        k = kres_ref[0].astype(jnp.float32)  # (H, block_n, d_k)
        w, s, z = quant_block_tile(
            k, bits=bits, granularity=k_gran, param_dtype=param_dtype
        )
        kw_out[0] = w
        ks_out[0] = s
        kz_out[0] = z
        if not shared_kv:
            v = vres_ref[0].astype(jnp.float32)
            wv, sv, zv = quant_block_tile(
                v, bits=bits, granularity="tensor", param_dtype=param_dtype
            )
            vw_out[0] = wv
            vs_out[0] = sv
            vz_out[0] = zv

    @pl.when(jnp.logical_not(full))
    def _keep():
        # pool page dest_page[b] is this sequence's private scratch page (the
        # caller's injectivity contract); restore the fetched input block
        kw_out[...] = kw_in[...]
        ks_out[...] = ks_in[...]
        kz_out[...] = kz_in[...]
        if not shared_kv:
            vw_out[...] = vw_in[...]
            vs_out[...] = vs_in[...]
            vz_out[...] = vz_in[...]


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block_n", "k_gran", "shared_kv", "interpret"),
)
def paged_residual_flush_pallas(
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
    bits: int,
    block_n: int,
    k_gran: str,
    shared_kv: bool = False,
    interpret: bool,
):
    """Commit ``k_res[b]``/``v_res[b]`` into pool page ``dest_page[b]`` of the
    shared ``[P, H, ...]`` page pools for every sequence with ``full[b] != 0``;
    other sequences' destination pages pass through untouched (callers point
    them at per-slot scratch pages so destinations stay pairwise distinct).
    Returns the six updated pool arrays (V side ``None`` when ``shared_kv`` —
    the MLA latent pools have no V stream), aliased in place on TPU.
    """
    n_pages, h, npr, d_k = kw_pool.shape
    b = k_res.shape[0]
    param_dtype = k_scale_pool.dtype
    if not interpret:
        minor = aliased_minor_dims(
            d_k, None if shared_kv else vw_pool.shape[-1], block_n, k_gran,
            shared_kv,
        )
        if any(m % 128 for m in minor):
            raise ValueError(
                "paged_residual_flush_pallas writes the pools in place and "
                f"cannot lane-pad them: minor dims {minor} must all be "
                "multiples of 128 on TPU — use impl='xla' for this shape"
            )

    def pool_spec(*minor):
        # page dest_page[b] of every head; the clamp keeps the DMA in range
        # (callers never pass out-of-pool pages)
        return pl.BlockSpec(
            (1, h, *minor),
            lambda i, f, dr: (jnp.minimum(dr[i], n_pages - 1), 0) + (0,) * len(minor),
        )

    def res_spec(d):
        return pl.BlockSpec((1, h, block_n, d), lambda i, f, dr: (i, 0, 0, 0))

    w_spec = pool_spec(npr, d_k)
    kp_spec = pool_spec(d_k if k_gran == "channel" else block_n)
    kres_spec = res_spec(d_k)

    if shared_kv:
        pool_specs = [w_spec, kp_spec, kp_spec]
        pools = [kw_pool, k_scale_pool, k_zero_pool]
        in_specs = [kres_spec] + pool_specs
        operands = [k_res] + pools
        n_lead = 3  # full, dest_page, k_res precede the aliased pools
    else:
        d_v = vw_pool.shape[-1]
        vw_spec = pool_spec(npr, d_v)
        vp_spec = pool_spec(block_n)
        vres_spec = res_spec(d_v)
        pool_specs = [w_spec, kp_spec, kp_spec, vw_spec, vp_spec, vp_spec]
        pools = [kw_pool, k_scale_pool, k_zero_pool, vw_pool, v_scale_pool,
                 v_zero_pool]
        in_specs = [kres_spec, vres_spec] + pool_specs
        operands = [k_res, v_res] + pools
        n_lead = 4  # full, dest_page, k_res, v_res precede the aliased pools
    out_shape = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pools]

    # alias each pool input onto its output; indices count the scalar-prefetch
    # operands (full, dest_page) and the residual inputs
    aliases = {n_lead + i: i for i in range(len(pools))}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=pool_specs,
    )
    body = functools.partial(
        _paged_body, bits=bits, k_gran=k_gran, shared_kv=shared_kv,
        param_dtype=param_dtype,
    )
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="paged_residual_flush",
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
    )(full.astype(jnp.int32), dest_page.astype(jnp.int32), *operands)
    if shared_kv:
        kw_pool, k_scale_pool, k_zero_pool = out
        return kw_pool, k_scale_pool, k_zero_pool, None, None, None
    return tuple(out)
