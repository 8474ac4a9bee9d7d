"""Shared model layers: norms, RoPE / M-RoPE, MLPs, embeddings."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.params import P

# ---------------------------------------------------------------- norms


def rmsnorm_def(d: int):
    return {"w": P((d,), ("embed",), "ones", jnp.float32)}


def rmsnorm(p, x, *, eps: float = 1e-6, plus_one: bool = False):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    w = p["w"] + 1.0 if plus_one else p["w"]
    return (y * w).astype(x.dtype)


def layernorm_def(d: int):
    return {"w": P((d,), ("embed",), "ones", jnp.float32),
            "b": P((d,), ("embed",), "zeros", jnp.float32)}


def layernorm(p, x, *, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["w"] + p["b"]).astype(x.dtype)


def norm_def(kind: str, d: int):
    return layernorm_def(d) if kind == "ln" else rmsnorm_def(d)


def apply_norm(kind: str, p, x, **kw):
    return layernorm(p, x) if kind == "ln" else rmsnorm(p, x, **kw)


# ---------------------------------------------------------------- RoPE


def rope_freqs(head_dim: int, theta: float = 10000.0):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature factor ``0.1 * mscale * ln(factor) + 1``
    (1 at factor <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, factor: float, beta_fast: float,
               beta_slow: float, original_max_pos: int):
    """YaRN inverse frequencies [head_dim/2] (DeepSeek-V3's
    ``DeepseekV3YarnRotaryEmbedding``): bands that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    bands that turn fewer than ``beta_slow`` times are divided by
    ``factor``, and a linear ramp blends the two in between."""
    def correction_dim(rotations):
        return (head_dim * math.log(original_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = rope_freqs(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(x, positions, *, theta: float = 10000.0, sections=None,
               freqs=None, scale: float = 1.0):
    """x: [B, S, H, d]; positions: [B, S] int (or [3, B, S] for M-RoPE).

    M-RoPE (Qwen2-VL §3): the rotary frequency bands are split into
    ``sections = (t, h, w)`` groups (summing to d/2); each group consumes its
    own position stream — temporal for text, (h, w) grid for image patches.
    ``freqs`` ([d/2]) replaces the plain ``theta`` frequencies (YaRN) and
    ``scale`` multiplies cos and sin (YaRN's mscale ratio).
    """
    b, s, h, d = x.shape
    if freqs is None:
        freqs = rope_freqs(d, theta)  # [d/2]
    if sections is None:
        ang = positions.astype(jnp.float32)[:, :, None] * freqs[None, None, :]
    else:
        assert positions.ndim == 3, "M-RoPE needs positions [3, B, S]"
        parts = []
        off = 0
        for i, sec in enumerate(sections):
            ang_i = positions[i].astype(jnp.float32)[:, :, None] * freqs[None, None, off : off + sec]
            parts.append(ang_i)
            off += sec
        ang = jnp.concatenate(parts, axis=-1)  # [B, S, d/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- MLP


def mlp_def(d: int, d_ff: int, act: str, bias: bool = False):
    defs = {}
    if act in ("swiglu", "geglu"):
        defs["wi"] = P((d, 2 * d_ff), ("embed", "mlp"))
    else:
        defs["wi"] = P((d, d_ff), ("embed", "mlp"))
    defs["wo"] = P((d_ff, d), ("mlp", "embed"))
    if bias:
        defs["bi"] = P((defs["wi"].shape[-1],), ("mlp",), "zeros", jnp.float32)
        defs["bo"] = P((d,), ("embed",), "zeros", jnp.float32)
    return defs


def mlp(p, x, act: str):
    h = jnp.einsum("...d,df->...f", x, p["wi"])
    if "bi" in p:
        h = h + p["bi"].astype(h.dtype)
    if act == "swiglu":
        u, g = jnp.split(h, 2, axis=-1)
        h = u * jax.nn.silu(g)
    elif act == "geglu":
        u, g = jnp.split(h, 2, axis=-1)
        h = u * jax.nn.gelu(g)
    elif act == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(act)
    out = jnp.einsum("...f,fd->...d", h, p["wo"])
    if "bo" in p:
        out = out + p["bo"].astype(out.dtype)
    return out


# ---------------------------------------------------------------- embeddings


def embed_def(vocab: int, d: int):
    return {"table": P((vocab, d), ("vocab", "embed"), "embed")}


def embed(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def unembed_def(d: int, vocab: int):
    return {"w": P((d, vocab), ("embed", "vocab"), "normal")}


def unembed(p, x, true_vocab: int | None = None):
    logits = jnp.einsum("...d,dv->...v", x, p["w"]).astype(jnp.float32)
    return mask_padded_vocab(logits, true_vocab)


def mask_padded_vocab(logits, true_vocab: int | None):
    """Mask logits of vocab-padding ids (see ArchConfig.padded_vocab)."""
    v = logits.shape[-1]
    if true_vocab is None or true_vocab == v:
        return logits
    ids = jax.lax.broadcasted_iota(jnp.int32, (1, v), 1)[0]
    return jnp.where(ids[None, None, :] < true_vocab, logits, -1e30)
