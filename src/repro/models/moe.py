"""Mixture-of-Experts layers: the routers, the expert share that serving
runs, and the capacity-based dispatch that training runs.

Routing (:func:`route`) is over every one of the layer's ``n_experts``:
softmax or sigmoid scores, top-k, optionally DeepSeek-V3's group-limited
``noaux_tc`` choice (a correction bias on the choice scores only, groups
scored by their two best, ``router_topk_groups`` groups kept), normalised
weights times ``routed_scaling``.

Serving (:func:`moe_share`) is dropless: the model holds the
``n_experts / expert_chips`` experts of rank ``expert_rank`` (all of them at
one chip) and computes, for every (token, expert) pair whose expert it
holds, the routed weight times that expert's SwiGLU, as one grouped matmul
over the pairs sorted by expert (``megablox.gmm`` on TPU), plus the shared
expert.  The other ranks' parts are not computed here.

Training (:func:`moe_ffn`) keeps token-choice top-k with capacity-based
group-local dispatch: tokens are processed in groups (one sequence = one
group by default); the dispatch sort/positioning is *within-group*
(vmapped), so under pjit with the group dimension sharded along (pod, data)
the routing math is local to a data shard and the only cross-device
movement is the dispatched activations being resharded onto the
expert-parallel (model) axis — the all-to-all pattern, inserted by the SPMD
partitioner at the sharding-constraint boundary.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers
from repro.models.params import P

#: tokens per grouped-matmul pass of the expert share: a longer prefill
#: runs in chunks of this many, so its pair buffers stay a few hundred MB
MOE_CHUNK = 2048


def moe_def(cfg) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_expert
    held = cfg.held_experts
    defs = {
        # the router keeps its full width: it scores every expert
        "router": P((d, e), ("embed", None), "normal", jnp.float32),
        # inner expert dims use 'expert_mlp' (replicated): the expert axis
        # itself carries the model-parallel (EP) sharding
        "wi": P((held, d, 2 * f), ("experts", "embed", "expert_mlp")),
        "wo": P((held, f, d), ("experts", "expert_mlp", "embed")),
    }
    if cfg.router_bias:
        defs["router_bias"] = P((e,), (None,), "zeros", jnp.float32)
    if cfg.n_shared_experts:
        defs["shared"] = layers.mlp_def(d, cfg.n_shared_experts * f, cfg.act)
    return defs


def _capacity(cfg, group_tokens: int) -> int:
    c = max(1, int(group_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    # decode (1-token groups): a token's top-k experts are distinct, so
    # capacity 1 is exact — the old floor of 8 inflated decode expert
    # compute 8x.  Align to 8 sublanes only once the capacity warrants it.
    return c if c < 8 else -(-c // 8) * 8


def route(p, cfg, x):
    """Router over all ``cfg.n_experts`` experts: ``x [..., d]`` ->
    ``(weights [..., k] f32, experts [..., k] int32, logits [..., E])``."""
    # float32 throughout, as published: a bf16 pass could flip a choice
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"],
                        precision=lax.Precision.HIGHEST)
    if cfg.router_score == "sigmoid":  # DeepSeek-V3 style
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + p["router_bias"] if cfg.router_bias else scores
    if cfg.router_groups:
        grouped = choice.reshape(*choice.shape[:-1], cfg.router_groups, -1)
        group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = lax.top_k(group_score, cfg.router_topk_groups)
        kept = jnp.any(keep[..., None] == jnp.arange(cfg.router_groups), axis=-2)
        choice = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(choice.shape)
    _, top_e = lax.top_k(choice, cfg.top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg.router_norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-9)
    return top_w * cfg.routed_scaling, top_e, logits


def gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """The grouped matmul's (rows, contraction, output) tile: 128 rows, and
    up to 1024 x 1024 of an expert's matrix (2 MB in bf16) per grid step."""
    return min(128, m), min(k, 1024), min(n, 1024)


def _grouped(lhs, rhs, sizes, out_dtype, impl):
    """Rows of ``lhs [m, k]``, sorted by group, times their group's matrix of
    ``rhs [g, k, n]``; ``sizes [g + 1]`` counts each group's rows, the last
    entry the trailing rows of no held group, which come out zero."""
    if impl == "pallas":
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(lhs, rhs, sizes, preferred_element_type=out_dtype,
                   tiling=gmm_tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2]),
                   interpret=jax.default_backend() != "tpu")
    return lax.ragged_dot(lhs, rhs, sizes[:-1], preferred_element_type=out_dtype)


def _share_chunk(p, cfg, xt, impl):
    """:func:`moe_share` over ``xt [T, d]`` tokens in one grouped pass."""
    t, d = xt.shape
    k, held = cfg.top_k, p["wi"].shape[-3]
    with jax.named_scope("moe.route"):
        w, e, _ = route(p, cfg, xt)
        local = e - cfg.expert_rank * held
        here = (local >= 0) & (local < held)
        # pairs sorted by held expert; every other pair (and the padding
        # up to a whole row tile) falls in one trailing group of no expert
        key = jnp.where(here, local, held).reshape(-1)
        m = t * k
        tm = 128 if m >= 128 else -(-m // 16) * 16
        pad = -m % tm
        key = jnp.concatenate([key, jnp.full((pad,), held, key.dtype)])
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=held + 1).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(m + pad, dtype=order.dtype))
    wi, wo, groups = p["wi"], p["wo"], sizes
    if "layer" in p:
        # every layer's experts, one group each; only this layer's are sized
        n = wi.shape[0] * held
        wi, wo = wi.reshape(n, *wi.shape[2:]), wo.reshape(n, *wo.shape[2:])
        groups = lax.dynamic_update_slice(
            jnp.zeros((n + 1,), jnp.int32).at[n].set(sizes[held]), sizes[:held],
            (p["layer"] * held,))
    with jax.named_scope("moe.experts"):
        lhs = xt[jnp.minimum(order // k, t - 1)].astype(wi.dtype)
        h = _grouped(lhs, wi, groups, jnp.float32, impl)
        u, g = jnp.split(h, 2, axis=-1)
        a = (u * jax.nn.silu(g)).astype(wo.dtype)
        y = _grouped(a, wo, groups, wo.dtype, impl)
    y = y[inverse[:m]].reshape(t, k, d).astype(jnp.float32)
    # rows of pairs held elsewhere are never computed: select, not multiply
    out = jnp.sum(jnp.where(here[..., None], w[..., None] * y, 0.0), axis=1)
    counts = jnp.stack([jnp.sum(here), jnp.sum(sizes[:held] > 0)]).astype(jnp.int32)
    return out, counts


def moe_share(p, cfg, x, *, impl: str = "auto"):
    """Dropless expert share: ``x [..., d]`` -> ``(out [..., d], counts)``.

    Every token is routed over all ``cfg.n_experts`` (:func:`route`); for
    each pair whose expert this model holds (rank ``cfg.expert_rank``'s
    ``held_experts``), the routed weight times the expert's SwiGLU, summed
    per token, plus the shared expert.  With ``p["layer"]`` (a layer scan,
    see ``models/transformer._scan_params``) ``p["wi"]``/``p["wo"]`` hold
    every layer's experts and the layer's own are picked by group.  ``counts`` (int32 ``[2]``) are the
    pairs computed here and the held experts with at least one pair.
    ``impl``: 'pallas' (megablox grouped matmul; interpret mode off-TPU),
    'xla' (``lax.ragged_dot``) or 'auto' (pallas on TPU)."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    t = xt.shape[0]
    if t <= MOE_CHUNK:
        out, counts = _share_chunk(p, cfg, xt, impl)
    else:
        n = -(-t // MOE_CHUNK)
        xc = jnp.pad(xt, ((0, n * MOE_CHUNK - t), (0, 0))).reshape(n, MOE_CHUNK, -1)
        out, counts = lax.map(lambda c: _share_chunk(p, cfg, c, impl), xc)
        out, counts = out.reshape(-1, shape[-1])[:t], jnp.sum(counts, axis=0)
    if cfg.n_shared_experts:
        out = out + layers.mlp(p["shared"], xt, cfg.act).astype(jnp.float32)
    return out.astype(x.dtype).reshape(shape), counts


def moe_ffn(p, cfg, x):
    """x: [B, S, d] -> [B, S, d].  Groups = sequences (B is the group dim)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    if cfg.held_experts != e:
        raise ValueError("the capacity dispatch runs whole layers; an expert "
                         "share serves through moe_share")
    cap = _capacity(cfg, s)
    top_w, top_e, logits = route(p, cfg, x)  # [B, S, k]

    def dispatch_one(xg, eg, wg):
        # xg [S, d], eg [S, k] expert ids, wg [S, k] weights — one group.
        flat_e = eg.reshape(-1)  # [S*k]
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # [S*k, E]
        # position within the expert's capacity buffer (0-based)
        pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1  # [S*k]
        keep = (pos >= 0) & (pos < cap)
        src = jnp.repeat(jnp.arange(s), k)  # token index per slot
        # scatter tokens into [E, cap, d]
        xe = jnp.zeros((e, cap, d), x.dtype)
        xe = xe.at[flat_e, jnp.where(keep, pos, cap - 1)].add(
            jnp.where(keep[:, None], xg[src], 0).astype(x.dtype)
        )
        return xe, (flat_e, pos, keep, src)

    xe, meta = jax.vmap(dispatch_one)(x, top_e, top_w)  # [B, E, cap, d]

    # expert-parallel resharding boundary: dispatched tokens move onto the
    # expert (model) axis here — the all-to-all pattern — instead of letting
    # the partitioner replicate the dispatch tensors (§Perf iteration B)
    from repro.dist.sharding import constrain

    xe = constrain(xe, ("pod", "data"), "model", None, None)
    # expert FFN (SwiGLU), experts sharded on the model axis (EP)
    h = jnp.einsum("becd,edf->becf", xe, p["wi"])
    u, g = jnp.split(h, 2, axis=-1)
    h = u * jax.nn.silu(g)
    ye = jnp.einsum("becf,efd->becd", h, p["wo"])  # [B, E, cap, d]
    ye = constrain(ye, ("pod", "data"), "model", None, None)

    def combine_one(ye_g, wg, m):
        flat_e, pos, keep, src = m
        vals = ye_g[flat_e, jnp.clip(pos, 0, cap - 1)]  # [S*k, d]
        vals = jnp.where(keep[:, None], vals, 0)
        w = wg.reshape(-1)[:, None].astype(vals.dtype)
        out = jnp.zeros((s, d), vals.dtype).at[src].add(vals * w)
        return out

    out = jax.vmap(combine_one)(ye, top_w, meta)
    if cfg.n_shared_experts:
        out = out + layers.mlp(p["shared"], x, cfg.act)
    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=(0, 1, 2))
    pe = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=(0, 1))
    aux = e * jnp.sum(me * pe)
    return out.astype(x.dtype), aux
