"""Plain reference of a dense decoder with grouped-query attention and a
low-bit KV cache (Qwen2), in ``jax.numpy`` and float32 at
``Precision.HIGHEST``.  It imports nothing of the program.

What it computes, for one sequence of prompt and served tokens:

- pre-norm blocks: RMSNorm; q/k/v projections with bias; rotary
  embedding over the two halves of each head; causal softmax attention,
  query head ``h`` reading KV head ``h // (n_heads / n_kv_heads)``; output
  projection; SwiGLU (``up * silu(gate)``) MLP with an optional bias; a
  final RMSNorm and the LM head (tied or not).
- the KV cache as the configuration states it: keys and values are held as
  they are until their block of ``kv_block`` tokens is complete, then as
  ``kv_bits``-bit asymmetric min-max codes with float16 scale and zero:
  K per channel over the block's tokens, V per token over its channels.
  The prompt attends itself unquantized (its prefill runs before any cache
  is read); a decoded token at position ``t`` reads every key of a complete
  block (block index below ``(t + 1) // kv_block``) dequantized, the rest
  as they are.

The weights are drawn here from the seed (``init_weights``), in the
layout of the serving program's parameter tree, so that both sides read
the same numbers and neither takes them from the other.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest float8_e4m3fn value


# ------------------------------------------------------------------ weights


def _dims(conf):
    d, hq = conf["hidden_size"], conf["num_attention_heads"]
    return (d, hq, conf["num_key_value_heads"], conf["head_dim"],
            conf["intermediate_size"], conf["vocab_size"],
            conf["num_hidden_layers"])


def weight_shapes(conf) -> dict:
    """``{path: (shape, dtype, kind, std)}`` of every leaf, in the serving
    program's layout (``stack_0`` holds the layers on a leading axis)."""
    d, hq, hkv, hd, ff, v, n = _dims(conf)
    bf, f32 = jnp.bfloat16, jnp.float32
    leaves = {
        "embed/table": ((v, d), bf, "normal", 0.02),
        "final_norm/w": ((d,), f32, "one", 0.05),
        "stack_0/ln1/w": ((n, d), f32, "one", 0.05),
        "stack_0/ln2/w": ((n, d), f32, "one", 0.05),
        "stack_0/attn/wq": ((n, d, hq, hd), bf, "normal", d ** -0.5),
        "stack_0/attn/wk": ((n, d, hkv, hd), bf, "normal", d ** -0.5),
        "stack_0/attn/wv": ((n, d, hkv, hd), bf, "normal", d ** -0.5),
        "stack_0/attn/wo": ((n, hq, hd, d), bf, "normal", (hq * hd) ** -0.5),
        "stack_0/mlp/wi": ((n, d, 2 * ff), bf, "normal", d ** -0.5),
        "stack_0/mlp/wo": ((n, ff, d), bf, "normal", ff ** -0.5),
    }
    if conf["qkv_bias"]:
        leaves["stack_0/attn/bq"] = ((n, hq, hd), f32, "normal", 0.1)
        leaves["stack_0/attn/bk"] = ((n, hkv, hd), f32, "normal", 0.1)
        leaves["stack_0/attn/bv"] = ((n, hkv, hd), f32, "normal", 0.1)
        # the program carries an MLP bias whenever q/k/v have one; a
        # published MLP without bias holds it at zero
        zero = not conf["mlp_bias"]
        leaves["stack_0/mlp/bi"] = ((n, 2 * ff), f32,
                                    "zero" if zero else "normal", 0.05)
        leaves["stack_0/mlp/bo"] = ((n, d), f32, "zero" if zero else "normal", 0.05)
    if not conf["tie_word_embeddings"]:
        leaves["unembed/w"] = ((d, v), bf, "normal", d ** -0.5)
    return leaves


def _draw(key, shape, dtype, kind, std):
    if kind == "zero":
        return jnp.zeros(shape, dtype)
    x = jax.random.normal(key, shape, jnp.float32) * std
    return (1.0 + x if kind == "one" else x).astype(dtype)


def init_weights(conf, key) -> dict:
    """Every weight from ``key`` in one jitted call, in the served dtype.
    Stacked leaves are drawn layer by layer inside the call, so no stacked
    float32 copy is ever held."""
    leaves = weight_shapes(conf)

    @jax.jit
    def make(key):
        out = {}
        for i, (path, (shape, dtype, kind, std)) in enumerate(sorted(leaves.items())):
            k = jax.random.fold_in(key, i)
            if path.startswith("stack_0/") and len(shape) > 2:
                keys = jax.random.split(k, shape[0])
                out[path] = lax.map(
                    lambda kk, s=shape[1:], dt=dtype, kd=kind, sd=std:
                    _draw(kk, s, dt, kd, sd), keys)
            else:
                out[path] = _draw(k, shape, dtype, kind, std)
        return out

    flat = make(key)
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *head, last = path.split("/")
        for p in head:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


# ------------------------------------------------------------------ layers


def _fp8(x, axis):
    """``x`` rounded to float8_e4m3fn with one scale per slice along
    ``axis`` (the largest magnitude maps to the largest fp8 value)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    """``a [T, K] @ w [K, N]`` in float32; with ``fp8`` both operands are
    first rounded to scaled fp8 (per row of ``a``, per column of ``w``),
    the lower-precision control."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        a, w = _fp8(a, 1), _fp8(w, 0)
    return jnp.dot(a, w, precision=HIGHEST)


def _norm(conf, p, x):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * lax.rsqrt(var + conf["rms_norm_eps"]) * p["w"]


def _rope(x, pos, theta):
    """x [T, H, hd]: rotate the pair (i, i + hd/2) by pos * theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fake_quant(x, bits, axis):
    """Asymmetric min-max quantization along ``axis`` with float16 scale and
    zero, returned dequantized."""
    qmax = 2 ** bits - 1
    lo = jnp.min(x, axis=axis, keepdims=True)
    hi = jnp.max(x, axis=axis, keepdims=True)
    scale = jnp.maximum((hi - lo) / qmax, 1e-6).astype(jnp.float16).astype(jnp.float32)
    zero = lo.astype(jnp.float16).astype(jnp.float32)
    q = jnp.clip(jnp.round((x - zero) / scale), 0, qmax)
    return q * scale + zero


def cache_view(k, v, bits, block, k_gran):
    """Dequantized K and V [T, H, hd] as the cache holds a complete block:
    K per channel over the block (``k_gran="channel"``) or per token, V per
    token.  T is a multiple of ``block``."""
    t, h, hd = k.shape
    kb = k.reshape(t // block, block, h, hd)
    kq = _fake_quant(kb, bits, 1 if k_gran == "channel" else 3).reshape(t, h, hd)
    vq = _fake_quant(v, bits, 2)
    return kq, vq


def _attention(conf, q, k, v, kq, vq, prompt_len, q_chunk):
    """Causal attention of every position, each reading keys as the cache
    held them when that position ran (see the module docstring)."""
    t, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    block = conf["engine"]["kv_block"]
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(t, hkv, g, hd)
    kpos = jnp.arange(t)

    def chunk(i):
        rows = i * q_chunk + jnp.arange(q_chunk)
        qc = lax.dynamic_slice_in_dim(qg, i * q_chunk, q_chunk, 0)
        s_raw = jnp.einsum("thgd,shd->hgts", qc, k, precision=HIGHEST) * scale
        s_deq = jnp.einsum("thgd,shd->hgts", qc, kq, precision=HIGHEST) * scale
        causal = kpos[None, :] <= rows[:, None]
        deq = ((rows[:, None] >= prompt_len)
               & (kpos[None, :] // block < (rows[:, None] + 1) // block))
        s = jnp.where(deq, s_deq, s_raw)
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = (jnp.einsum("hgts,shd->thgd", jnp.where(deq, p, 0.0), vq, precision=HIGHEST)
             + jnp.einsum("hgts,shd->thgd", jnp.where(deq, 0.0, p), v, precision=HIGHEST))
        return o.reshape(q_chunk, hq * hd)

    return lax.map(chunk, jnp.arange(t // q_chunk)).reshape(t, hq * hd)


def _layer(conf, fp8, prompt_len, q_chunk, x, lp):
    d, hq, hkv, hd, ff, _, _ = _dims(conf)
    t = x.shape[0]
    eng = conf["engine"]
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    pos = jnp.arange(t)
    a = lp["attn"]
    h = _norm(conf, lp["ln1"], x)
    q = _mm(h, a["wq"].reshape(d, hq * hd), fp8).reshape(t, hq, hd)
    k = _mm(h, a["wk"].reshape(d, hkv * hd), fp8).reshape(t, hkv, hd)
    v = _mm(h, a["wv"].reshape(d, hkv * hd), fp8).reshape(t, hkv, hd)
    if conf["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q, pos, conf["rope_theta"])
    k = _rope(k, pos, conf["rope_theta"])
    kq, vq = cache_view(k, v, eng["kv_bits"], eng["kv_block"], eng["kv_gran"])
    o = _attention(conf, q, k, v, kq, vq, prompt_len, q_chunk)
    x = x + _mm(o, a["wo"].reshape(hq * hd, d), fp8)
    m = lp["mlp"]
    h = _norm(conf, lp["ln2"], x)
    u = _mm(h, m["wi"], fp8)
    if "bi" in m:
        u = u + m["bi"]
    up, gate = u[:, :ff], u[:, ff:]
    u = up * jax.nn.silu(gate)
    f = _mm(u, m["wo"], fp8)
    if "bo" in m:
        f = f + m["bo"]
    return x + f, None


@functools.partial(jax.jit, static_argnames=("conf_json", "fp8", "q_chunk", "v_chunk"))
def _scores(weights, tokens, prompt_len, targets, *, conf_json, fp8, q_chunk, v_chunk):
    conf = json.loads(conf_json)
    x = jnp.take(weights["embed"]["table"], tokens, axis=0).astype(jnp.float32)
    x, _ = lax.scan(functools.partial(_layer, conf, fp8, prompt_len, q_chunk),
                    x, weights["stack_0"])
    x = _norm(conf, jax.tree.map(lambda a: a.astype(jnp.float32), weights["final_norm"]), x)
    head = (weights["embed"]["table"].T if conf["tie_word_embeddings"]
            else weights["unembed"]["w"])
    t = x.shape[0]

    def rows(i):
        xc = lax.dynamic_slice_in_dim(x, i * v_chunk, v_chunk, 0)
        logits = _mm(xc, head, fp8)
        tc = lax.dynamic_slice_in_dim(targets, i * v_chunk, v_chunk, 1)
        at = jnp.take_along_axis(logits[None], tc[:, :, None], axis=2)[..., 0]
        return logits.max(-1), jnp.argmax(logits, -1).astype(jnp.int32), at

    mx, am, at = lax.map(rows, jnp.arange(t // v_chunk))
    return (mx.reshape(t), am.reshape(t),
            jnp.moveaxis(at, 0, 1).reshape(targets.shape[0], t))


def scores(conf, weights, tokens, prompt_len, targets, *, fp8=False,
           q_chunk=256, v_chunk=256):
    """For each position of ``tokens`` ([T] int32, T a multiple of the
    chunks and of ``kv_block``): the largest logit, its token, and the logit
    of each row of ``targets`` ([n, T] int32).  ``prompt_len`` counts the
    prompt's tokens; later positions are decoded ones."""
    return _scores(weights, tokens, jnp.int32(prompt_len), targets,
                   conf_json=json.dumps(conf, sort_keys=True), fp8=fp8,
                   q_chunk=q_chunk, v_chunk=v_chunk)
