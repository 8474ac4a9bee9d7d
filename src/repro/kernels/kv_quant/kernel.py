"""Pallas TPU kernel: fused quantize + strided pack (paper's Residual Kernel).

Grid = (B, n_blocks); one program quantizes block ``k`` of every KV head,
an (H, block_n, d) tile:
  1. min/max reduction on the VPU (channel-wise: over the token/sublane axis;
     tensor-wise: over the channel/lane axis) — the TPU analogue of the
     paper's __shfl_xor_sync warp reductions, which Mosaic owns at VREG level;
  2. in-register scale/zero computation ("half2" pairs, stored bf16/f16);
  3. in-register quantize (round/clip) and strided bit-pack (shift+or) so the
     packed words land directly in the layout the decode kernel's unpack
     reproduces in natural token order (core/layout.py).

All tiles live in VMEM via BlockSpec; no HBM round-trip between the
quantization statistics and the pack — the paper's "fused computation and
quantization within fragments" (§IV-A(1)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core import layout

_F32_BIG = 3.0e38  # python float: jnp scalars would be captured consts in pallas
_EPS = 1e-6


def quant_block_tile(x, *, bits, granularity, param_dtype, d_orig=None):
    """Quantize + strided-pack f32 ``(..., block_n, d)`` tiles, in registers.

    Shared by the prefill-time kv_quant kernel and the decode-time
    residual_flush kernel so both commit bitwise-identical packed blocks.
    Leading dims (e.g. the KV heads of one step) are independent tiles.
    ``d_orig`` masks lane padding out of the tensor-granularity stats (pass
    None / d when the tile is unpadded).  Returns
    ``(words (..., npr, d) int32, scale, zero)`` with params cast to
    ``param_dtype`` *before* quantizing, so codes are consistent with what
    the decode kernel will dequantize with.
    """
    block_n, d_pad = x.shape[-2:]
    qmax = layout.qmax(bits)

    if granularity == "channel":
        # stats along the token (sublane) axis, one pair per channel
        xmin = jnp.min(x, axis=-2)
        xmax = jnp.max(x, axis=-2)
        scale = jnp.maximum((xmax - xmin) / qmax, _EPS).astype(param_dtype)
        zero = xmin.astype(param_dtype)
        sf, zf = scale.astype(jnp.float32), zero.astype(jnp.float32)
        q = jnp.round((x - zf[..., None, :]) / sf[..., None, :])
    elif granularity == "tensor":
        # stats along the channel (lane) axis, one pair per token
        if d_orig is not None and d_pad != d_orig:
            lane = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
            valid = lane < d_orig
            xmin = jnp.min(jnp.where(valid, x, _F32_BIG), axis=-1)
            xmax = jnp.max(jnp.where(valid, x, -_F32_BIG), axis=-1)
        else:
            xmin = jnp.min(x, axis=-1)
            xmax = jnp.max(x, axis=-1)
        scale = jnp.maximum((xmax - xmin) / qmax, _EPS).astype(param_dtype)
        zero = xmin.astype(param_dtype)
        sf, zf = scale.astype(jnp.float32), zero.astype(jnp.float32)
        q = jnp.round((x - zf[..., :, None]) / sf[..., :, None])
    else:
        raise ValueError(granularity)

    q = jnp.clip(q, 0, qmax).astype(jnp.int32)

    # strided pack: word[i] collects bit-plane k from token k*npr + i
    shifts, _ = layout.plane_shift_mask(bits)
    npr = layout.words_per_block(block_n, bits)
    w = q[..., 0:npr, :] << shifts[0]
    for k in range(1, len(shifts)):
        w = w | (q[..., k * npr : (k + 1) * npr, :] << shifts[k])
    return w, scale, zero


def _kvquant_kernel(
    x_ref, w_ref, s_ref, z_ref, *, bits, d_orig, granularity, param_dtype
):
    x = x_ref[0].astype(jnp.float32)  # (H, block_n, d_pad)
    w, scale, zero = quant_block_tile(
        x, bits=bits, granularity=granularity, param_dtype=param_dtype,
        d_orig=d_orig,
    )
    s_ref[0, 0] = scale
    z_ref[0, 0] = zero
    w_ref[0] = w


@functools.partial(
    jax.jit,
    static_argnames=(
        "bits",
        "granularity",
        "block_n",
        "param_dtype",
        "interpret",
    ),
)
def quantize_kv_pallas(
    x: jnp.ndarray,
    *,
    bits: int,
    granularity: str,
    block_n: int = 128,
    param_dtype=jnp.bfloat16,
    interpret: bool = False,
):
    """x: [B, H, S, d] (S % block_n == 0) -> (words, scale, zero).

    d is padded to a multiple of 128 lanes internally; outputs keep padded d
    for channel-wise params/words (callers slice) — here we slice back to the
    original d so the public contract matches ref.py exactly.
    """
    b, h, s, d = x.shape
    if s % block_n:
        raise ValueError(f"S={s} not a multiple of block_n={block_n}")
    nb = s // block_n
    npr = layout.words_per_block(block_n, bits)

    d_pad = max(128, -(-d // 128) * 128)
    if d_pad != d:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, d_pad - d)))

    # params are written block-major ([B, nb, H, p]): a step's (H, p) rows
    # form a legal TPU tile, one head's (1, p) row of [B, H, nb, p] does not
    p = d_pad if granularity == "channel" else block_n
    param_spec = pl.BlockSpec((1, 1, h, p), lambda i, k: (i, k, 0, 0))

    kernel = functools.partial(
        _kvquant_kernel,
        bits=bits,
        d_orig=d,
        granularity=granularity,
        param_dtype=param_dtype,
    )
    words, scale, zero = pl.pallas_call(
        kernel,
        grid=(b, nb),
        in_specs=[pl.BlockSpec((1, h, block_n, d_pad), lambda i, k: (i, 0, k, 0))],
        out_specs=[
            pl.BlockSpec((1, h, npr, d_pad), lambda i, k: (i, 0, k, 0)),
            param_spec,
            param_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nb * npr, d_pad), jnp.int32),
            jax.ShapeDtypeStruct((b, nb, h, p), param_dtype),
            jax.ShapeDtypeStruct((b, nb, h, p), param_dtype),
        ],
        interpret=interpret,
        name="kv_quant",
    )(x)

    words = words.reshape(b, h, nb, npr, d_pad)
    scale, zero = jnp.swapaxes(scale, 1, 2), jnp.swapaxes(zero, 1, 2)
    if d_pad != d:
        words = words[..., :d]
        if granularity == "channel":
            scale = scale[..., :d]
            zero = zero[..., :d]
    return words, scale, zero
