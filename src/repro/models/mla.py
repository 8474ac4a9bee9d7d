"""Multi-head Latent Attention (DeepSeek-V2/V3) with a quantized latent cache.

Train uses the expanded form; decode uses the *absorbed* form, where queries
are projected into the latent space (q @ W_uk) and attention runs directly
against the cached latent stream ``[c_kv ; k_rope]``.  BitDecoding applies to
the latent cache itself (shared_kv mode): one quantized stream feeds both the
score and value sides, and g_q = n_heads (128) — the query transformation's
best case, a fully-populated MXU M dimension.

The latent stream is stored lane-aligned: ``[c_kv ; k_rope ; 0]`` padded to
:func:`latent_width` (a multiple of 128; 640 for DeepSeek-V3's 512 + 64), so
the pools, the residual tail and the flush are allocated at the width the
TPU kernels tile and no call pads them again.  The zero channels quantize to
zero and the absorbed query carries zeros there.

Rotary embedding covers the ``qk_rope`` channels of q and of the latent,
with YaRN's frequencies and softmax-scale factor where the config sets
``yarn_factor`` (DeepSeek-V3: 40).  The rope channels use the rotate-half
layout; the published checkpoint's interleaved pairs are a fixed
permutation of the ``q_up`` and ``kv_down`` rope columns.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import attention as catt
from repro.core import qcache
from repro.models import layers
from repro.models.params import P


def mla_def(cfg) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    ql, kvl = cfg.q_lora, cfg.kv_lora
    dn, dr, dv = cfg.qk_nope, cfg.qk_rope, cfg.v_head_dim
    return {
        "q_down": P((d, ql), ("embed", None)),
        "q_norm": layers.rmsnorm_def(ql),
        "q_up": P((ql, h, dn + dr), (None, "heads", "head_dim")),
        "kv_down": P((d, kvl + dr), ("embed", None)),
        "kv_norm": layers.rmsnorm_def(kvl),
        "k_up": P((kvl, h, dn), (None, "heads", "head_dim")),
        "v_up": P((kvl, h, dv), (None, "heads", "head_dim")),
        "wo": P((h, dv, d), ("heads", "head_dim", "embed")),
    }


def latent_width(cfg) -> int:
    """Stored width of one latent token: kv_lora + qk_rope, lane-aligned."""
    return -(-(cfg.kv_lora + cfg.qk_rope) // 128) * 128


def _pad_latent(cfg, x):
    """Zero channels after ``[c_kv ; k_rope]`` (or the absorbed query) up to
    :func:`latent_width`."""
    pad = latent_width(cfg) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _rope(cfg, x, positions):
    """RoPE over the last dim of ``x [B,S,H,qk_rope]``; YaRN's frequencies
    and cos/sin factor when ``cfg.yarn_factor > 1``."""
    if cfg.yarn_factor <= 1:
        return layers.apply_rope(x, positions, theta=cfg.rope_theta)
    freqs = layers.yarn_freqs(cfg.qk_rope, cfg.rope_theta, cfg.yarn_factor,
                              cfg.yarn_beta_fast, cfg.yarn_beta_slow,
                              cfg.yarn_original_max_pos)
    scale = (layers.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / layers.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return layers.apply_rope(x, positions, freqs=freqs, scale=scale)


def sm_scale(cfg) -> float:
    """Softmax scale 1/sqrt(qk_nope + qk_rope), times YaRN's mscale^2
    (``mscale_all_dim``) when it is on."""
    s = 1.0 / (cfg.qk_nope + cfg.qk_rope) ** 0.5
    if cfg.yarn_factor > 1 and cfg.yarn_mscale_all_dim:
        s *= layers.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return s


def _latent(p, cfg, x, positions):
    """x [B,S,d] -> (c_kv [B,S,kv_lora], k_rope [B,S,qk_rope]) with RoPE."""
    kvr = jnp.einsum("bsd,dl->bsl", x, p["kv_down"])
    c_kv = layers.rmsnorm(p["kv_norm"], kvr[..., : cfg.kv_lora])
    k_rope = kvr[..., cfg.kv_lora :]
    k_rope = _rope(cfg, k_rope[:, :, None, :], positions)[:, :, 0]
    return c_kv, k_rope


def _queries(p, cfg, x, positions):
    c_q = layers.rmsnorm(p["q_norm"], jnp.einsum("bsd,dl->bsl", x, p["q_down"]))
    q = jnp.einsum("bsl,lhk->bshk", c_q, p["q_up"])
    q_nope = q[..., : cfg.qk_nope]
    q_rope = _rope(cfg, q[..., cfg.qk_nope :], positions)
    return q_nope, q_rope


def mla_train(p, cfg, x, positions):
    """Expanded-form training attention."""
    b, s, d = x.shape
    q_nope, q_rope = _queries(p, cfg, x, positions)
    c_kv, k_rope = _latent(p, cfg, x, positions)
    k_nope = jnp.einsum("bsl,lhk->bshk", c_kv, p["k_up"])
    k_rope_h = jnp.broadcast_to(
        k_rope[:, :, None, :], (b, s, cfg.n_heads, cfg.qk_rope)
    ).astype(k_nope.dtype)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    v = jnp.einsum("bsl,lhk->bshk", c_kv, p["v_up"])
    # §Perf iteration B2: the concat of differently-sharded parts (nope from
    # the FSDP-sharded up-projection, rope replicated) otherwise makes the
    # partitioner shard the score dot's CONTRACTION dim -> a partial-sum
    # all-reduce of every (S x block) score tile, ~64 TB/device at 32K.
    # Pin q/k/v to batch x head sharding before attention.
    from repro.dist.sharding import constrain

    q = constrain(q, ("pod", "data"), None, "model", None)
    k = constrain(k, ("pod", "data"), None, "model", None)
    v = constrain(v, ("pod", "data"), None, "model", None)
    out = catt.blockwise_attention(
        q, k, v, causal=True,
        sm_scale=sm_scale(cfg), block_k=cfg.attn_block_k,
    )
    return jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])


def mla_init_cache(cfg, batch: int, max_seq: int, *, block_align=None):
    """Latent cache: one KV 'head' of :func:`latent_width`, shared_kv."""
    return qcache.init_cache(
        batch, 1, latent_width(cfg), max_seq,
        bits=cfg.kv_bits, block_n=cfg.kv_block, k_gran="channel", shared_kv=True,
        block_align=block_align,
    )


def mla_init_paged_cache(cfg, n_pages: int, batch: int, nb_max: int):
    """Paged latent cache (serving engine layout): the single quantized
    latent stream lives in shared ``shared_kv`` page pools — no V-side pools
    at all — and decodes through ``kernels/paged_bitdecode``'s latent walk."""
    return qcache.init_paged_cache(
        n_pages, batch, 1, latent_width(cfg), nb_max,
        bits=cfg.kv_bits, block_n=cfg.kv_block, k_gran="channel",
        shared_kv=True,
    )


def _expand_latent(p, cfg, lat):
    """Latent ``[B, T, kv_lora + qk_rope]`` -> expanded per-head
    (k [B,T,h,qk_nope+qk_rope], v [B,T,h,v_head_dim]) via the up-projections.

    Algebraically the absorbed decode score ``q_eff · lat`` equals the
    expanded ``q · k`` (``q_nope·(c@W_uk) == (q_nope@W_uk)·c``), so attending
    an expanded *dequantized* latent prior gives the suffix prefill the same
    numeric view of shared pages that paged latent decode has.
    """
    b, t = lat.shape[:2]
    c = lat[..., : cfg.kv_lora]
    r = lat[..., cfg.kv_lora : cfg.kv_lora + cfg.qk_rope]
    k_nope = jnp.einsum("btl,lhk->bthk", c, p["k_up"])
    k = jnp.concatenate(
        [k_nope,
         jnp.broadcast_to(r[:, :, None, :], (b, t, cfg.n_heads, cfg.qk_rope)
                          ).astype(k_nope.dtype)],
        axis=-1,
    )
    v = jnp.einsum("btl,lhk->bthk", c, p["v_up"])
    return k, v


def mla_prefill_cache(p, cfg, x, positions, max_seq: int, *, quant_impl="auto",
                      lengths=None, block_align=None, prior=None,
                      prior_len=None):
    """Prefill attention + latent cache build.

    ``prior`` (prefix sharing, serving engine) is the dequantized shared
    latent prior ``(lat [B, T, 1, kv_lora+qk_rope], None)`` from
    ``qcache.dequant_prior`` on a shared_kv paged cache: ``x`` holds only the
    divergent suffix, whose expanded Q/K/V attend the expanded prior through
    :func:`repro.core.attention.prefix_suffix_attention` (callers pass
    suffix-global ``positions``).  The built cache holds suffix latents only.
    """
    c_kv, k_rope = _latent(p, cfg, x, positions)
    if prior is None:
        out = mla_train(p, cfg, x, positions)
    else:
        b, s = x.shape[:2]
        q_nope, q_rope = _queries(p, cfg, x, positions)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_nope = jnp.einsum("bsl,lhk->bshk", c_kv, p["k_up"])
        k = jnp.concatenate(
            [k_nope,
             jnp.broadcast_to(k_rope[:, :, None, :],
                              (b, s, cfg.n_heads, cfg.qk_rope)
                              ).astype(k_nope.dtype)],
            axis=-1,
        )
        v = jnp.einsum("bsl,lhk->bshk", c_kv, p["v_up"])
        k_prior, v_prior = _expand_latent(p, cfg, prior[0][:, :, 0, :])
        out = catt.prefix_suffix_attention(
            q, k, v, k_prior, v_prior, prior_len,
            sm_scale=sm_scale(cfg),
        )
        out = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])
    lat = _pad_latent(cfg, jnp.concatenate([c_kv, k_rope], axis=-1))[:, None]
    cache = mla_init_cache(cfg, x.shape[0], max_seq, block_align=block_align)
    cache = qcache.prefill(cache, lat, None, lengths=lengths, quant_impl=quant_impl)
    return out, cache


def mla_decode(p, cfg, x, positions, cache, *, impl="auto", quant_impl="auto"):
    """Absorbed-form decode against the quantized latent cache."""
    b = x.shape[0]
    q_nope, q_rope = _queries(p, cfg, x, positions)  # [B,1,h,*]
    c_kv, k_rope = _latent(p, cfg, x, positions)
    # [B, H=1, S=1, latent_width]
    lat = _pad_latent(cfg, jnp.concatenate([c_kv, k_rope], axis=-1))[:, None]
    with jax.named_scope("mla.absorb"):
        # absorb: q_eff = [q_nope @ W_uk ; q_rope ; 0]  -> latent_width
        q_lat = jnp.einsum("bshk,lhk->bshl", q_nope, p["k_up"])  # [B,1,h,kv_lora]
        q_eff = _pad_latent(cfg, jnp.concatenate([q_lat, q_rope], axis=-1))
    out_lat, cache = catt.decode_append_attention(
        q_eff, cache, lat, None, quant_impl=quant_impl,
        sm_scale=sm_scale(cfg), d_v=cfg.kv_lora, impl=impl,
    )  # [B,1,h,kv_lora]
    with jax.named_scope("mla.absorb"):
        out = jnp.einsum("bshl,lhk->bshk", out_lat.astype(x.dtype), p["v_up"])
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache
