"""Plain reference of DeepSeek-V3's blocks (multi-head latent attention
with YaRN, a dense SwiGLU layer, and MoE layers with the ``noaux_tc``
router) over a low-bit latent cache, in ``jax.numpy`` and float32 at
``Precision.HIGHEST``.  It imports nothing of the program.

What it computes, for one sequence of prompt and served tokens, as the
published model does (``modeling_deepseek.py``), on this chip's share of the
configuration's deployment:

- pre-norm blocks: RMSNorm; MLA in its expanded form: ``c_q =
  RMSNorm(x W_dq)``, ``q = c_q W_uq`` split into ``qk_nope`` and ``qk_rope``
  channels per head; ``[c_kv ; k_pe] = x W_dkv`` with ``c_kv`` normed;
  ``k = [c_kv W_uk ; k_pe]`` (``k_pe`` shared by every head), ``v = c_kv
  W_uv``; causal softmax attention at scale ``mscale^2 / sqrt(qk_nope +
  qk_rope)``; the output projection.  Rotary embedding on ``q_pe`` and
  ``k_pe`` with YaRN's frequencies (a linear ramp between ``beta_fast`` and
  ``beta_slow`` over the correction range of the original context) in the
  rotate-half layout;
- the first ``stage_dense_layers`` layers with a SwiGLU MLP; the others with
  the MoE block: sigmoid scores of the float32 router over all
  ``n_routed_experts * expert_chips`` experts; the choice made on score +
  ``e_score_correction_bias`` over ``n_group`` groups, each scored by the sum
  of its two best, keeping ``topk_group`` groups and then the top
  ``num_experts_per_tok`` experts; the weights the unbiased scores,
  normalised and times ``routed_scaling_factor``.  Of the routed part only
  the experts this chip holds (rank ``expert_rank``'s ``n_routed_experts``)
  contribute, each its weight times its SwiGLU; the shared expert is added;
- a final RMSNorm and the LM head over the configuration's (sliced)
  vocabulary.
- the latent cache as the configuration states it: the latent ``[c_kv ;
  k_pe]`` of a token is held as it is until its block of ``kv_block``
  tokens is complete, then as ``kv_bits``-bit min-max codes per channel over
  the block with float16 scale and zero.  Both the score side (``c_kv W_uk``
  and ``k_pe``) and the value side (``c_kv W_uv``) read that one dequantized
  stream.  The prompt attends itself unquantized; a decoded token at
  position ``t`` reads every key of a complete block (block index below
  ``(t + 1) // kv_block``) dequantized, the rest as they are.

Attention runs in query chunks so that 128 heads over the cell's contexts
fit beside the weights.  The weights are drawn here from the seed
(``init_weights``), in the layout of the serving program's parameter tree.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.reference.dense_gqa import HIGHEST, _draw, _fake_quant, _mm, _norm


# ------------------------------------------------------------------ weights


def _dims(conf):
    return dict(
        d=conf["hidden_size"], h=conf["num_attention_heads"], ql=conf["q_lora_rank"],
        kvl=conf["kv_lora_rank"], dn=conf["qk_nope_head_dim"],
        dr=conf["qk_rope_head_dim"], dv=conf["v_head_dim"],
        ff=conf["intermediate_size"], f=conf["moe_intermediate_size"],
        held=conf["n_routed_experts"],
        e=conf["n_routed_experts"] * conf["expert_chips"],
        v=conf["vocab_size"], nd=conf["stage_dense_layers"],
        nm=conf["num_hidden_layers"] - conf["stage_dense_layers"])


def weight_shapes(conf) -> dict:
    """``{path: (shape, dtype, kind, std)}`` of every leaf, in the serving
    program's layout: ``stack_0`` holds the dense layers and ``stack_1`` the
    MoE layers, each on a leading layer axis."""
    m = _dims(conf)
    d, h, ql, kvl, dn, dr, dv = (m[k] for k in ("d", "h", "ql", "kvl", "dn", "dr", "dv"))
    bf, f32 = jnp.bfloat16, jnp.float32
    leaves = {
        "embed/table": ((m["v"], d), bf, "normal", 0.02),
        "final_norm/w": ((d,), f32, "one", 0.05),
        "unembed/w": ((d, m["v"]), bf, "normal", d ** -0.5),
    }
    for stack, n in (("stack_0", m["nd"]), ("stack_1", m["nm"])):
        leaves.update({
            f"{stack}/ln1/w": ((n, d), f32, "one", 0.05),
            f"{stack}/ln2/w": ((n, d), f32, "one", 0.05),
            f"{stack}/attn/q_down": ((n, d, ql), bf, "normal", d ** -0.5),
            f"{stack}/attn/q_norm/w": ((n, ql), f32, "one", 0.05),
            f"{stack}/attn/q_up": ((n, ql, h, dn + dr), bf, "normal", ql ** -0.5),
            f"{stack}/attn/kv_down": ((n, d, kvl + dr), bf, "normal", d ** -0.5),
            f"{stack}/attn/kv_norm/w": ((n, kvl), f32, "one", 0.05),
            f"{stack}/attn/k_up": ((n, kvl, h, dn), bf, "normal", kvl ** -0.5),
            f"{stack}/attn/v_up": ((n, kvl, h, dv), bf, "normal", kvl ** -0.5),
            f"{stack}/attn/wo": ((n, h, dv, d), bf, "normal", (h * dv) ** -0.5),
        })
    n, f, fs = m["nm"], m["f"], m["f"] * conf["n_shared_experts"]
    leaves.update({
        "stack_0/mlp/wi": ((m["nd"], d, 2 * m["ff"]), bf, "normal", d ** -0.5),
        "stack_0/mlp/wo": ((m["nd"], m["ff"], d), bf, "normal", m["ff"] ** -0.5),
        "stack_1/moe/router": ((n, d, m["e"]), f32, "normal", d ** -0.5),
        "stack_1/moe/router_bias": ((n, m["e"]), f32, "normal", 0.1),
        "stack_1/moe/wi": ((n, m["held"], d, 2 * f), bf, "normal", d ** -0.5),
        "stack_1/moe/wo": ((n, m["held"], f, d), bf, "normal", f ** -0.5),
        "stack_1/moe/shared/wi": ((n, d, 2 * fs), bf, "normal", d ** -0.5),
        "stack_1/moe/shared/wo": ((n, fs, d), bf, "normal", fs ** -0.5),
    })
    return leaves


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
            if path.startswith("stack_") and len(shape) > 2:
                out[path] = lax.map(
                    lambda kk, s=shape[1:], dt=dtype, kd=kind, sd=std:
                    _draw(kk, s, dt, kd, sd), jax.random.split(k, shape[0]))
            else:
                out[path] = _draw(k, shape, dtype, kind, std)
        return out

    tree: dict = {}
    for path, leaf in make(key).items():
        node = tree
        *head, last = path.split("/")
        for p in head:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


# ------------------------------------------------------------------ layers


def yarn(conf) -> tuple[np.ndarray, float, float]:
    """YaRN's inverse frequencies over the ``qk_rope`` channels, the factor on
    cos and sin, and the softmax scale's factor (``mscale^2``), from the
    configuration's ``rope_scaling`` as the published model computes them."""
    rs, dim, base = conf["rope_scaling"], conf["qk_rope_head_dim"], float(conf["rope_theta"])
    factor = float(rs["factor"])

    def correction_dim(rot):
        return (dim * math.log(rs["original_max_position_embeddings"] / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    def get_mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / factor
    keep = 1.0 - np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    inv = inter * (1 - keep) + extra * keep
    cos_sin = get_mscale(rs["mscale"]) / get_mscale(rs["mscale_all_dim"])
    return inv.astype(np.float32), cos_sin, get_mscale(rs["mscale_all_dim"]) ** 2


def _rope(x, pos, inv, cos_sin):
    """x [T, H, dr]: rotate the pair (i, i + dr/2) by pos * inv[i]."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos = jnp.cos(ang)[:, None, :] * cos_sin
    sin = jnp.sin(ang)[:, None, :] * cos_sin
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def route(conf, p, x):
    """The ``noaux_tc`` router over every expert: ``x [T, d]`` ->
    (weights [T, k], experts [T, k]); float32 whatever the operands' control."""
    m = _dims(conf)
    scores = jax.nn.sigmoid(jnp.dot(x, p["router"].astype(jnp.float32), precision=HIGHEST))
    choice = scores + p["router_bias"]
    t, g = x.shape[0], conf["n_group"]
    grouped = choice.reshape(t, g, m["e"] // g)
    group_scores = jnp.sum(lax.top_k(grouped, 2)[0], -1)
    _, keep = lax.top_k(group_scores, conf["topk_group"])
    mask = jnp.zeros((t, g), bool).at[jnp.arange(t)[:, None], keep].set(True)
    choice = jnp.where(mask[:, :, None], grouped, -jnp.inf).reshape(t, m["e"])
    _, experts = lax.top_k(choice, conf["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, experts, 1)
    if conf["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w * conf["routed_scaling_factor"], experts


def _swiglu(x, wi, wo, fp8):
    u = _mm(x, wi, fp8)
    ff = u.shape[-1] // 2
    return _mm(u[:, :ff] * jax.nn.silu(u[:, ff:]), wo, fp8)


def moe(conf, p, x, fp8):
    """The MoE block's part on this chip: the held experts' routed part plus
    the shared expert."""
    m = _dims(conf)
    w, experts = route(conf, p, x)
    first = conf["expert_rank"] * m["held"]

    def expert(acc, xs):
        j, wi, wo = xs
        wj = jnp.sum(jnp.where(experts == first + j, w, 0.0), -1)
        return acc + wj[:, None] * _swiglu(x, wi, wo, fp8), None

    out, _ = lax.scan(expert, jnp.zeros_like(x),
                      (jnp.arange(m["held"]), p["wi"], p["wo"]))
    return out + _swiglu(x, p["shared"]["wi"], p["shared"]["wo"], fp8)


def _attention(conf, a, h, prompt_len, q_chunk, fp8):
    """MLA over the sequence, each position reading the latent as the cache
    held it when that position ran (see the module docstring)."""
    m = _dims(conf)
    hh, kvl, dn, dr, dv = m["h"], m["kvl"], m["dn"], m["dr"], m["dv"]
    eng = conf["engine"]
    block = eng["kv_block"]
    t = h.shape[0]
    pos = jnp.arange(t)
    inv, cos_sin, mscale2 = yarn(conf)
    scale = mscale2 / math.sqrt(dn + dr)
    cq = _norm(conf, a["q_norm"], _mm(h, a["q_down"], fp8))
    q = _mm(cq, a["q_up"].reshape(m["ql"], hh * (dn + dr)), fp8).reshape(t, hh, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, inv, cos_sin)
    kv = _mm(h, a["kv_down"], fp8)
    c = _norm(conf, a["kv_norm"], kv[:, :kvl])
    k_pe = _rope(kv[:, None, kvl:], pos, inv, cos_sin)[:, 0]
    lat = jnp.concatenate([c, k_pe], -1)
    lat_q = _fake_quant(lat.reshape(t // block, block, kvl + dr), eng["kv_bits"], 1
                        ).reshape(t, kvl + dr)

    def expand(latent):
        cc = latent[:, :kvl]
        k_nope = _mm(cc, a["k_up"].reshape(kvl, hh * dn), fp8).reshape(t, hh, dn)
        v = _mm(cc, a["v_up"].reshape(kvl, hh * dv), fp8).reshape(t, hh, dv)
        return k_nope, latent[:, kvl:], v

    raw, deq = expand(lat), expand(lat_q)

    def scores(qn, qp, k):
        return (jnp.einsum("thd,shd->hts", qn, k[0], precision=HIGHEST)
                + jnp.einsum("thd,sd->hts", qp, k[1], precision=HIGHEST)) * scale

    def chunk(i):
        rows = i * q_chunk + jnp.arange(q_chunk)
        qn = lax.dynamic_slice_in_dim(q_nope, i * q_chunk, q_chunk, 0)
        qp = lax.dynamic_slice_in_dim(q_pe, i * q_chunk, q_chunk, 0)
        use_deq = ((rows[:, None] >= prompt_len)
                   & (pos[None, :] // block < (rows[:, None] + 1) // block))
        s = jnp.where(use_deq, scores(qn, qp, deq), scores(qn, qp, raw))
        s = jnp.where(pos[None, :] <= rows[:, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        o = (jnp.einsum("hts,shd->thd", jnp.where(use_deq, pr, 0.0), deq[2], precision=HIGHEST)
             + jnp.einsum("hts,shd->thd", jnp.where(use_deq, 0.0, pr), raw[2],
                          precision=HIGHEST))
        return o.reshape(q_chunk, hh * dv)

    o = lax.map(chunk, jnp.arange(t // q_chunk)).reshape(t, hh * dv)
    return _mm(o, a["wo"].reshape(hh * dv, m["d"]), fp8)


def _layer(conf, fp8, prompt_len, q_chunk, kind, x, lp):
    h = _norm(conf, lp["ln1"], x)
    x = x + _attention(conf, lp["attn"], h, prompt_len, q_chunk, fp8)
    h = _norm(conf, lp["ln2"], x)
    if kind == "moe":
        return x + moe(conf, lp["moe"], h, fp8), None
    return x + _swiglu(h, lp["mlp"]["wi"], lp["mlp"]["wo"], fp8), None


@functools.partial(jax.jit, static_argnames=("conf_json", "fp8", "q_chunk", "v_chunk"))
def _scores(weights, tokens, prompt_len, targets, *, conf_json, fp8, q_chunk, v_chunk):
    conf = json.loads(conf_json)
    x = jnp.take(weights["embed"]["table"], tokens, axis=0).astype(jnp.float32)
    for stack, kind in (("stack_0", "mlp"), ("stack_1", "moe")):
        x, _ = lax.scan(functools.partial(_layer, conf, fp8, prompt_len, q_chunk, kind),
                        x, weights[stack])
    x = _norm(conf, weights["final_norm"], x)
    head = weights["unembed"]["w"]
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
           q_chunk=128, v_chunk=256):
    """For each position of ``tokens`` ([T] int32, T a multiple of the
    chunks and of ``kv_block``): the largest logit, its token, and the logit
    of each row of ``targets`` ([n, T] int32).  ``prompt_len`` counts the
    prompt's tokens; later positions are decoded ones.  With ``fp8`` every
    matmul with a bf16 weight takes scaled fp8 operands (the control); the
    float32 router and attention's own products do not."""
    return _scores(weights, tokens, jnp.int32(prompt_len), targets,
                   conf_json=json.dumps(conf, sort_keys=True), fp8=fp8,
                   q_chunk=q_chunk, v_chunk=v_chunk)
