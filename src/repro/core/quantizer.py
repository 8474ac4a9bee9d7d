"""Quantization policies for the low-bit KV cache (paper §V-B, Residual Kernel).

Two scaling granularities, matching the paper:

* **channel-wise** (K default, KIVI-style): statistics are taken *along the
  token axis* of a residual block, one (scale, zero) pair per channel per
  block.  Param shape per block: ``[d]``.
* **tensor-wise** (V always; K optional "KT" mode): statistics are taken
  *along the channel axis* per token, one pair per token (per channel-group
  of size ``group``).  Param shape per block: ``[block_n, d // group]``
  (``group == d`` → per-token scalar, stored ``[block_n]``).

Asymmetric uint quantization:  q = clip(round((x - zero) / scale)),
x̂ = q * scale + zero.  Params are stored in ``param_dtype`` (default
float16 — the paper's ``half2`` (scale, zero) pairs); all arithmetic is f32.
"""
from __future__ import annotations

from typing import Literal

import jax.numpy as jnp
from jax import lax

from repro.core import layout

Granularity = Literal["channel", "tensor"]

_EPS = 1e-6


def _minmax_params(xmin, xmax, bits, param_dtype):
    scale = (xmax - xmin) / layout.qmax(bits)
    scale = jnp.maximum(scale, _EPS)
    return scale.astype(param_dtype), xmin.astype(param_dtype)


def quant_params(
    x: jnp.ndarray,
    bits: int,
    granularity: Granularity,
    *,
    group: int | None = None,
    param_dtype=jnp.float16,
):
    """Compute (scale, zero) for a block x[..., block_n, d].

    channel-wise -> scale/zero [..., d]
    tensor-wise  -> scale/zero [..., block_n] (group=None/d) or
                    [..., block_n, d//group]
    """
    x = x.astype(jnp.float32)
    if granularity == "channel":
        xmin = jnp.min(x, axis=-2)
        xmax = jnp.max(x, axis=-2)
        return _minmax_params(xmin, xmax, bits, param_dtype)
    if granularity == "tensor":
        d = x.shape[-1]
        if group is None or group == d:
            xmin = jnp.min(x, axis=-1)
            xmax = jnp.max(x, axis=-1)
            return _minmax_params(xmin, xmax, bits, param_dtype)
        if d % group:
            raise ValueError(f"d={d} not divisible by group={group}")
        xg = x.reshape(*x.shape[:-1], d // group, group)
        xmin = jnp.min(xg, axis=-1)
        xmax = jnp.max(xg, axis=-1)
        return _minmax_params(xmin, xmax, bits, param_dtype)
    raise ValueError(f"unknown granularity {granularity!r}")


def _broadcast_params(p: jnp.ndarray, x_shape, granularity, group):
    """Broadcast (scale or zero) params to the element shape x[..., n, d]."""
    *_, n, d = x_shape
    if granularity == "channel":
        return p[..., None, :]  # [..., 1, d]
    if granularity == "tensor":
        if group is None or group == d:
            return p[..., :, None]  # per-token scalar [..., n] -> [..., n, 1]
        # grouped: [..., n, d//group] -> repeat along the channel group
        return jnp.repeat(p, group, axis=-1)
    raise ValueError(granularity)


def quantize_block(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    zero: jnp.ndarray,
    bits: int,
    granularity: Granularity,
    *,
    group: int | None = None,
) -> jnp.ndarray:
    """x[..., block_n, d] -> uint codes int32[..., block_n, d]."""
    xf = x.astype(jnp.float32)
    s = _broadcast_params(scale.astype(jnp.float32), x.shape, granularity, group)
    z = _broadcast_params(zero.astype(jnp.float32), x.shape, granularity, group)
    q = jnp.round((xf - z) / s)
    return jnp.clip(q, 0, layout.qmax(bits)).astype(jnp.int32)


def dequantize_block(
    q: jnp.ndarray,
    scale: jnp.ndarray,
    zero: jnp.ndarray,
    granularity: Granularity,
    *,
    group: int | None = None,
    dtype=jnp.bfloat16,
) -> jnp.ndarray:
    s = _broadcast_params(scale.astype(jnp.float32), q.shape, granularity, group)
    z = _broadcast_params(zero.astype(jnp.float32), q.shape, granularity, group)
    return (q.astype(jnp.float32) * s + z).astype(dtype)


def _held_at_own_precision(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` as f32 holding only values of ``x``'s own dtype.

    XLA may skip the rounding of a bf16 producer whose consumer widens it
    again (excess precision, on by default on TPU), so a bare
    ``astype(float32)`` can quantize values the cache never held; the Pallas
    kernels read the rounded operand.  ``reduce_precision`` keeps the
    rounding, and the codes bitwise equal to the kernels'."""
    xf = x.astype(jnp.float32)
    if jnp.issubdtype(x.dtype, jnp.floating) and jnp.finfo(x.dtype).bits < 32:
        fi = jnp.finfo(x.dtype)
        xf = lax.reduce_precision(xf, exponent_bits=fi.nexp, mantissa_bits=fi.nmant)
    return xf


def quantize_and_pack(
    x: jnp.ndarray,
    bits: int,
    granularity: Granularity,
    *,
    group: int | None = None,
    param_dtype=jnp.float16,
):
    """Fused reference path: block -> (words, scale, zero).

    x: [..., block_n, d] -> words int32[..., block_n // R, d].
    """
    x = _held_at_own_precision(x)
    scale, zero = quant_params(x, bits, granularity, group=group, param_dtype=param_dtype)
    q = quantize_block(x, scale, zero, bits, granularity, group=group)
    return layout.pack_strided(q, bits), scale, zero


def unpack_and_dequantize(
    words: jnp.ndarray,
    scale: jnp.ndarray,
    zero: jnp.ndarray,
    bits: int,
    granularity: Granularity,
    *,
    group: int | None = None,
    dtype=jnp.bfloat16,
):
    q = layout.unpack_strided(words, bits)
    return dequantize_block(q, scale, zero, granularity, group=group, dtype=dtype)
