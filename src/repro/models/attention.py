"""Model-level attention block: projections + RoPE + BitDecoding cache.

Train/prefill path uses the blockwise flash attention; the decode path
appends to the QuantKVCache and runs the fused low-bit kernel through the
query transformation (core/attention.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import attention as catt
from repro.core import qcache
from repro.models import layers
from repro.models.params import P


def _hq(cfg) -> int:
    return max(cfg.n_heads, cfg.n_heads_pad or 0)


def attn_def(cfg) -> dict:
    d, hq, hkv, hd = cfg.d_model, _hq(cfg), cfg.n_kv_heads, cfg.head_dim
    # fan-in scaling over the contracted dims (d in, hq*hd out); the default
    # reads the second-to-last dim, a head count here, which left random
    # attention logits in the hundreds and the softmax one-hot
    s_in, s_out = d**-0.5, (hq * hd) ** -0.5
    defs = {
        "wq": P((d, hq, hd), ("embed", "heads", "head_dim"), scale=s_in),
        "wk": P((d, hkv, hd), ("embed", "kv_heads", "head_dim"), scale=s_in),
        "wv": P((d, hkv, hd), ("embed", "kv_heads", "head_dim"), scale=s_in),
        "wo": P((hq, hd, d), ("heads", "head_dim", "embed"), scale=s_out),
    }
    if cfg.attn_bias:
        defs["bq"] = P((hq, hd), ("heads", "head_dim"), "zeros", jnp.float32)
        defs["bk"] = P((hkv, hd), ("kv_heads", "head_dim"), "zeros", jnp.float32)
        defs["bv"] = P((hkv, hd), ("kv_heads", "head_dim"), "zeros", jnp.float32)
    if cfg.qk_norm:
        defs["qnorm"] = layers.rmsnorm_def(hd)
        defs["knorm"] = layers.rmsnorm_def(hd)
    return defs


def _qkv(p, cfg, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.attn_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    if cfg.qk_norm:
        q = layers.rmsnorm(p["qnorm"], q)
        k = layers.rmsnorm(p["knorm"], k)
    if cfg.rope:
        q = layers.apply_rope(q, positions, theta=cfg.rope_theta, sections=cfg.mrope_sections)
        k = layers.apply_rope(k, positions, theta=cfg.rope_theta, sections=cfg.mrope_sections)
    return q, k, v


def attn_train(p, cfg, x, positions, *, causal=True):
    """x: [B, S, d] -> [B, S, d] (flash prefill/train attention)."""
    q, k, v = _qkv(p, cfg, x, positions)
    out = catt.blockwise_attention(q, k, v, causal=causal, block_k=cfg.attn_block_k)
    return jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])


def attn_prefill_cache(p, cfg, x, positions, max_seq: int, *, quant_impl="auto",
                       lengths=None, block_align=None, prior=None,
                       prior_len=None):
    """Run train attention AND build the quantized cache from the prefill K/V.

    ``lengths`` ([B] int32, optional) marks a ragged right-padded batch (the
    serve scheduler's bucketed prefill): per-sequence cache occupancy follows
    the true lengths, pad rows never become valid cache content.
    ``block_align`` rounds the cache's packed-block capacity up (mesh-aligned
    allocation for split-KV).

    ``prior`` (optional ``(k_prior, v_prior)``, each ``[B, T, H, d]``) marks a
    *suffix* prefill (prefix sharing): ``x`` holds only the divergent suffix
    tokens, whose attention also covers the first ``prior_len[b]`` prior
    tokens (dequantized shared pool pages, K already RoPE'd — see
    ``qcache.dequant_prior``).  The built cache holds suffix content only;
    the serving engine splices it behind the shared pages
    (``serve.pages.adopt_prefill(base_blocks=...)``).  Callers must pass
    suffix-global ``positions`` (``prior_len + arange``) so RoPE matches the
    unshared layout."""
    q, k, v = _qkv(p, cfg, x, positions)
    if prior is not None:
        out = catt.prefix_suffix_attention(q, k, v, *prior, prior_len)
    else:
        out = catt.blockwise_attention(q, k, v, causal=True, block_k=cfg.attn_block_k)
    cache = qcache.init_cache(
        x.shape[0], cfg.n_kv_heads, cfg.head_dim, max_seq,
        bits=cfg.kv_bits, block_n=cfg.kv_block, k_gran=cfg.kv_gran,
        block_align=block_align,
    )
    cache = qcache.prefill(
        cache, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        lengths=lengths, quant_impl=quant_impl,
    )
    return jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"]), cache


def attn_decode(p, cfg, x, positions, cache, *, impl="auto", quant_impl="auto",
                append=True):
    """x: [B, 1, d]; appends to cache (unless attending a static cross cache)
    then runs the fused low-bit decode kernel.  ``impl`` picks the attention
    kernel, ``quant_impl`` the residual-flush kernel."""
    q, k, v = _qkv(p, cfg, x, positions)
    if append:
        out, cache = catt.decode_append_attention(
            q, cache, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            quant_impl=quant_impl, impl=impl,
        )
    else:
        out = catt.decode_attention(q, cache, impl=impl)  # [B,1,hq,hd]
    return jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"]), cache


def cross_attn_def(cfg) -> dict:
    return attn_def(cfg)


def cross_attn_train(p, cfg, x, mem):
    """Encoder-decoder cross attention (training): full-precision."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", mem, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", mem, p["wv"])
    out = catt.blockwise_attention(q, k, v, causal=False, block_k=cfg.attn_block_k)
    return jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])


def build_cross_cache(p, cfg, mem, *, quant_impl="auto"):
    """Quantize the (static) encoder K/V once — the paper's Fig. 1a offline
    case, handled by the same Residual-Kernel machinery with the tail held in
    the residual buffer and never flushed."""
    k = jnp.einsum("btd,dhk->bthk", mem, p["wk"]).transpose(0, 2, 1, 3)
    v = jnp.einsum("btd,dhk->bthk", mem, p["wv"]).transpose(0, 2, 1, 3)
    cache = qcache.init_cache(
        mem.shape[0], cfg.n_kv_heads, cfg.head_dim, mem.shape[1],
        bits=cfg.kv_bits, block_n=cfg.kv_block, k_gran=cfg.kv_gran,
    )
    return qcache.prefill(cache, k, v, quant_impl=quant_impl)


def cross_attn_decode(p, cfg, x, cross_cache, *, impl="auto"):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    out = catt.decode_attention(q, cross_cache, impl=impl)
    return jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])
