"""Pallas TPU kernel: causal flash attention for prefill/training forward.

This is the train/prefill counterpart of the paged_bitdecode kernel: the XLA
attention path materializes every f32 score tile to HBM (S·block·heads per
step).  Here score tiles live entirely in VMEM: HBM traffic collapses to
Q/K/V/O once per block pair (K/V re-streamed per q-block — the flash
tradeoff).

Grid = (B, H_q, nq, nk), nk innermost with online-softmax carries in VMEM.
GQA is handled in the BlockSpec index maps (q head h reads kv head h // g) —
the training-time face of the paper's query transformation.  Blocks above
the causal diagonal are skipped (pl.when), the diagonal block is masked with
iota comparisons.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_VALUE = -1e37


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
            *, bq, bk, nk, s_valid, sm_scale, causal):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, MASK_VALUE, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    # causal block-level skip: kv block j starts after q block i ends
    live = (j * bk <= i * bq + (bq - 1)) if causal else (j >= 0)

    @pl.when(live)
    def _block():
        q = q_ref[0, 0].astype(jnp.bfloat16)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.bfloat16)  # (bk, d)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (bq, bk) — stays in VMEM
        rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = cols < s_valid
        if causal:
            valid = valid & (cols <= rows)
        s = jnp.where(valid, s, MASK_VALUE)

        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(
            p.astype(jnp.bfloat16), v_ref[0, 0].astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv
        m_scr[...] = m_next

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        # carries are lane-replicated (bq, 128); the transpose turns the
        # per-row lse into one lane-dense (1, bq) row
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l)).T[:1, :]


@functools.partial(
    jax.jit,
    static_argnames=("bq", "bk", "sm_scale", "causal", "s_valid", "interpret"),
)
def flash_prefill_pallas(
    q,  # [B, Hq, S_pad, d]  bf16 (pre-padded: S_pad % bq == 0 == % bk, d % 128)
    k,  # [B, Hkv, S_pad, d]
    v,  # [B, Hkv, S_pad, d]
    *,
    bq: int, bk: int, sm_scale: float, causal: bool, s_valid: int,
    interpret: bool,
):
    b, hq, s_pad, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    nq, nk = s_pad // bq, s_pad // bk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, h, i, j: (bi, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, h, i, j: (bi, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, h, i, j: (bi, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, h, i, j: (bi, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda bi, h, i, j: (bi, h, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    body = functools.partial(
        _kernel, bq=bq, bk=bk, nk=nk, s_valid=s_valid, sm_scale=sm_scale,
        causal=causal,
    )
    out, lse = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, s_pad, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, hq, 1, s_pad), jnp.float32),
        ],
        interpret=interpret,
        name="flash_prefill",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
    )(q, k, v)
    return out, lse[:, :, 0]
