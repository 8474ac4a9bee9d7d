"""Pallas TPU kernel: fused paged residual flush — the paper's Residual
Kernel proper (§V-B), decode-time face.

``qcache.paged_append_decode`` keeps the newest tokens in a bf16 residual
buffer and must, exactly once every ``block_n`` tokens, quantize that block
and commit it into the page pools.  This kernel does the whole flush in one
pass per sequence, every KV head at once (blocks ``(1, H, ...)``, whose
params form a legal tile):

  1. the residual tile is DMA'd HBM→VMEM once;
  2. min/max stats, scale/zero, round/clip and the strided bit-pack all run
     in registers (``kv_quant.kernel.quant_block_tile`` — the same code the
     prefill-time kernel uses, so flushed blocks are bitwise identical to
     prefill-quantized ones);
  3. the packed words + params are written *directly into the pools* via
     ``input_output_aliases``: the pools are donated, the output BlockSpec
     index map reads the destination pool page ``dest_page[b]`` from scalar
     prefetch, and only that one page is touched — no whole-pool copy.

The pools are layer-stacked (``[L, P, H, ...]``) and a scalar-prefetch
``layer`` picks the layer, so the decode layer scan flushes into the whole
stack in place and never copies one layer's pool out.

Per-sequence gating: ``full[b]`` (scalar prefetch) marks sequences whose
residual just filled.  Programs for non-full sequences copy their (aliased)
input page back unchanged — a one-page VMEM round-trip.  The kernel runs on
every decode step and relies on this per-row gate alone.  One invariant
follows from the aliasing: the per-sequence destinations must be pairwise
distinct, because two grid rows writing the same pool page would race.
Callers guarantee it by routing non-flushing sequences to a reserved
per-slot scratch page (pages ``[0, B)`` of every pool — see serve/pages.py);
flushing sequences always own distinct allocated pages.

Constraints (TPU, non-interpret): ``d % 128 == 0`` (the aliased pools cannot
be lane-padded in place — the kernel raises otherwise) and
``block_n % (32 // bits) == 0`` (layout invariant).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.kv_quant.kernel import quant_block_tile


def aliased_minor_dims(d_k, d_v, block_n, k_gran, shared_kv) -> list[int]:
    """Minor (lane) dims of every in-place aliased output: the packed words'
    head dims plus the block_n-wide rows of tensor-granularity params.  All
    must be 128-aligned on TPU (the aliased pools cannot be lane-padded in
    place)."""
    minor = [d_k] + ([block_n] if k_gran == "tensor" else [])
    if not shared_kv:
        minor += [d_v, block_n]
    return minor


def _paged_body(
    full_ref,
    dest_ref,
    layer_ref,
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
    layer,
    *,
    bits: int,
    block_n: int,
    k_gran: str,
    shared_kv: bool = False,
    interpret: bool,
):
    """Commit ``k_res[b]``/``v_res[b]`` into page ``dest_page[b]`` of layer
    ``layer`` (int32 scalar) of the layer-stacked ``[L, P, H, ...]`` page
    pools for every sequence with ``full[b] != 0``; other sequences'
    destination pages pass through untouched (callers point them at per-slot
    scratch pages so destinations stay pairwise distinct), and so does every
    other layer.  Returns the six updated pool arrays (V side ``None`` when
    ``shared_kv`` — the MLA latent pools have no V stream), aliased in place
    on TPU.
    """
    _, n_pages, h, npr, d_k = kw_pool.shape
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
        # page dest_page[b] of every head of layer `layer`; the clamp keeps
        # the DMA in range (callers never pass out-of-pool pages)
        return pl.BlockSpec(
            (pl.squeezed, 1, h, *minor),
            lambda i, f, dr, ly: (ly[0], jnp.minimum(dr[i], n_pages - 1), 0)
            + (0,) * len(minor),
        )

    def res_spec(d):
        return pl.BlockSpec((1, h, block_n, d), lambda i, f, dr, ly: (i, 0, 0, 0))

    w_spec = pool_spec(npr, d_k)
    kp_spec = pool_spec(d_k if k_gran == "channel" else block_n)
    kres_spec = res_spec(d_k)

    if shared_kv:
        pool_specs = [w_spec, kp_spec, kp_spec]
        pools = [kw_pool, k_scale_pool, k_zero_pool]
        in_specs = [kres_spec] + pool_specs
        operands = [k_res] + pools
        n_lead = 4  # full, dest_page, layer, k_res precede the aliased pools
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
        n_lead = 5  # full, dest_page, layer, k_res, v_res precede the pools
    out_shape = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pools]

    # alias each pool input onto its output; indices count the scalar-prefetch
    # operands (full, dest_page, layer) and the residual inputs
    aliases = {n_lead + i: i for i in range(len(pools))}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
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
    )(full.astype(jnp.int32), dest_page.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), *operands)
    if shared_kv:
        kw_pool, k_scale_pool, k_zero_pool = out
        return kw_pool, k_scale_pool, k_zero_pool, None, None, None
    return tuple(out)
