"""Decoder-only LM backbones with scan-over-layers.

Three backbone classes cover the assigned architecture families:

* :class:`DecoderLM`   — dense / MoE / MLA transformers (+ VLM stub front);
* :class:`HybridLM`    — Mamba2 backbone with a *shared* attention block every
                         ``attn_every`` layers (Zamba2's weight sharing: same
                         params, per-invocation KV cache);
* :class:`XLSTMLM`     — super-blocks of k mLSTM + 1 sLSTM.

All stacks store per-layer params with a leading ``layers`` axis and run
``lax.scan`` so HLO size is depth-independent; ``jax.checkpoint`` on the scan
body implements full-block remat for training.

Decode state is a plain dict pytree:
  {"caches": [per-stack stacked QuantKVCache], "ssm": ..., "pos": int32[B]}
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import qcache
from repro.models import attention as mattn
from repro.models import layers, mamba2, mla, moe, xlstm
from repro.models.family import PagedSpec
from repro.models.params import P, init_tree, shape_tree, spec_tree, stack


def _ce_loss(logits, labels, mask):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _positions_lm(b, s, offset=0):
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None] + offset, (b, s))


def _mrope_positions(cfg, b, s_total):
    """Stub M-RoPE position ids: image patches on a (t=0, h, w) grid, text
    continuing at offset max(grid)."""
    gh, gw = cfg.patch_grid
    p = cfg.n_patches
    idx = jnp.arange(p, dtype=jnp.int32)
    pt = jnp.zeros((p,), jnp.int32)
    ph, pw = idx // gw, idx % gw
    n_text = s_total - p
    toff = max(gh, gw)
    tpos = jnp.arange(n_text, dtype=jnp.int32) + toff
    t = jnp.concatenate([pt, tpos])
    h = jnp.concatenate([ph, tpos])
    w = jnp.concatenate([pw, tpos])
    pos = jnp.stack([t, h, w])  # [3, S]
    return jnp.broadcast_to(pos[:, None, :], (3, b, s_total))


def _mrope_decode_positions(cfg, pos):
    """pos [B] absolute index (incl. patch slots); text stream continues at
    offset max(grid) after the patch grid, matching _mrope_positions."""
    t = pos - cfg.n_patches + max(cfg.patch_grid)
    return jnp.broadcast_to(t[None, :, None], (3, pos.shape[0], 1))


class DecoderLM:
    """Dense / MoE / MLA decoder-only LM (optionally with VLM patch stub)."""

    def __init__(self, cfg):
        self.cfg = cfg
        if cfg.n_experts:
            fd = cfg.first_dense_layers
            self.stacks = ([("mlp", fd)] if fd else []) + [("moe", cfg.n_layers - fd)]
        elif cfg.d_ff:
            self.stacks = [("mlp", cfg.n_layers)]
        else:
            self.stacks = [("none", cfg.n_layers)]

    # ------------------------------------------------------------ params

    def _block_def(self, kind):
        cfg = self.cfg
        d = {"ln1": layers.norm_def(cfg.norm, cfg.d_model)}
        if cfg.mixer == "mla":
            d["attn"] = mla.mla_def(cfg)
        else:
            d["attn"] = mattn.attn_def(cfg)
        if kind == "mlp":
            d["mlp"] = layers.mlp_def(cfg.d_model, cfg.d_ff, cfg.act, cfg.attn_bias)
        elif kind == "moe":
            d["moe"] = moe.moe_def(cfg)
        if kind != "none" and not cfg.parallel_residual:
            d["ln2"] = layers.norm_def(cfg.norm, cfg.d_model)
        return d

    def param_defs(self):
        cfg = self.cfg
        defs: dict[str, Any] = {
            "embed": layers.embed_def(cfg.padded_vocab, cfg.d_model),
            "final_norm": layers.norm_def(cfg.norm, cfg.d_model),
        }
        if not cfg.tie_embeddings:
            defs["unembed"] = layers.unembed_def(cfg.d_model, cfg.padded_vocab)
        for i, (kind, n) in enumerate(self.stacks):
            defs[f"stack_{i}"] = stack(self._block_def(kind), n)
        if cfg.mtp:
            defs["mtp"] = {
                "norm": layers.norm_def(cfg.norm, cfg.d_model),
                "proj": P((cfg.d_model, cfg.d_model), ("embed", "mlp")),
            }
        return defs

    def init(self, rng):
        return init_tree(self.param_defs(), rng)

    def param_shapes(self):
        return shape_tree(self.param_defs())

    def param_specs(self, rules):
        return spec_tree(self.param_defs(), rules)

    # ------------------------------------------------------------ embedding

    def _embed(self, params, batch):
        cfg = self.cfg
        x = layers.embed(params["embed"], batch["tokens"])
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        if cfg.vision_stub:
            x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
        b, s = x.shape[0], x.shape[1]
        if cfg.mrope_sections:
            positions = _mrope_positions(cfg, b, s)
        else:
            positions = _positions_lm(b, s)
        return x, positions

    def _logits(self, params, x):
        cfg = self.cfg
        x = layers.apply_norm(cfg.norm, params["final_norm"], x, plus_one=cfg.rms_plus_one)
        if cfg.tie_embeddings:
            logits = jnp.einsum(
                "bsd,vd->bsv", x, params["embed"]["table"]
            ).astype(jnp.float32)
            return layers.mask_padded_vocab(logits, cfg.vocab)
        return layers.unembed(params["unembed"], x, cfg.vocab)

    # ------------------------------------------------------------ blocks

    def _mixer_train(self, p, x, positions):
        cfg = self.cfg
        if cfg.mixer == "mla":
            return mla.mla_train(p, cfg, x, positions)
        return mattn.attn_train(p, cfg, x, positions)

    def _block_train(self, p, kind, x, positions):
        cfg = self.cfg
        aux = jnp.float32(0.0)
        h = layers.apply_norm(cfg.norm, p["ln1"], x, plus_one=cfg.rms_plus_one)
        if cfg.parallel_residual:
            a = self._mixer_train(p["attn"], h, positions)
            f = layers.mlp(p["mlp"], h, cfg.act) if kind == "mlp" else 0.0
            return x + a + f, aux
        x = x + self._mixer_train(p["attn"], h, positions)
        if kind != "none":
            h2 = layers.apply_norm(cfg.norm, p["ln2"], x, plus_one=cfg.rms_plus_one)
            if kind == "moe":
                f, aux = moe.moe_ffn(p["moe"], cfg, h2)
            else:
                f = layers.mlp(p["mlp"], h2, cfg.act)
            x = x + f
        return x, aux

    def _run_stacks_train(self, params, x, positions):
        cfg = self.cfg
        aux_total = jnp.float32(0.0)

        for i, (kind, _) in enumerate(self.stacks):
            def body(carry, lp, _kind=kind):
                x, aux = carry
                x, a = self._block_train(lp, _kind, x, positions)
                return (x, aux + a), None

            if cfg.remat == "full":
                body = jax.checkpoint(body, prevent_cse=False)
            (x, aux_total), _ = lax.scan(body, (x, aux_total), params[f"stack_{i}"])
        return x, aux_total

    # ------------------------------------------------------------ train

    def loss(self, params, batch):
        cfg = self.cfg
        x, positions = self._embed(params, batch)
        x, aux = self._run_stacks_train(params, x, positions)
        logits = self._logits(params, x)
        if cfg.vision_stub:  # logits over text region only
            logits = logits[:, cfg.n_patches :]
        loss = _ce_loss(logits[:, :-1], batch["labels"][:, 1:], batch["loss_mask"][:, 1:])
        if cfg.mtp:  # simplified multi-token-prediction head: predict t+2
            h = layers.apply_norm(cfg.norm, params["mtp"]["norm"], x)
            h = jnp.einsum("bsd,df->bsf", h, params["mtp"]["proj"])
            logits2 = self._logits(params, h)
            if cfg.vision_stub:
                logits2 = logits2[:, cfg.n_patches :]
            loss = loss + 0.3 * _ce_loss(
                logits2[:, :-2], batch["labels"][:, 2:], batch["loss_mask"][:, 2:]
            )
        if cfg.n_experts:
            loss = loss + cfg.aux_loss_weight * aux / cfg.n_layers
        return loss

    # ------------------------------------------------------------ prefill

    def _block_prefill(self, p, kind, x, positions, max_seq, lengths=None,
                       block_align=None, prior=None, prior_len=None,
                       quant_impl="auto"):
        cfg = self.cfg
        h = layers.apply_norm(cfg.norm, p["ln1"], x, plus_one=cfg.rms_plus_one)
        if cfg.mixer == "mla":
            a, cache = mla.mla_prefill_cache(
                p["attn"], cfg, h, positions, max_seq, lengths=lengths,
                block_align=block_align, prior=prior, prior_len=prior_len,
                quant_impl=quant_impl,
            )
        else:
            a, cache = mattn.attn_prefill_cache(
                p["attn"], cfg, h, positions, max_seq, lengths=lengths,
                block_align=block_align, prior=prior, prior_len=prior_len,
                quant_impl=quant_impl,
            )
        if cfg.parallel_residual:
            f = layers.mlp(p["mlp"], h, cfg.act) if kind == "mlp" else 0.0
            return x + a + f, cache
        x = x + a
        if kind != "none":
            h2 = layers.apply_norm(cfg.norm, p["ln2"], x, plus_one=cfg.rms_plus_one)
            f = moe.moe_ffn(p["moe"], cfg, h2)[0] if kind == "moe" else layers.mlp(p["mlp"], h2, cfg.act)
            x = x + f
        return x, cache

    def prefill(self, params, batch, max_seq: int, *, lengths=None,
                block_align=None, prior=None, prior_len=None,
                quant_impl="auto"):
        """Process the prompt, build quantized caches, return (last_logits, state).

        ``lengths`` ([B] int32, optional): the batch is ragged — same-bucket
        prompts right-padded to a common static length (the serve
        scheduler's bucketed prefill).  Causality keeps real tokens blind to
        the right-pad, per-sequence cache occupancy follows the true lengths
        (``qcache.prefill``), and the returned logits are gathered at each
        sequence's last *real* token instead of the padded tail.
        ``block_align`` propagates mesh-aligned block allocation (split-KV).

        ``prior`` / ``prior_len`` turn this into a *suffix* prefill (prefix
        sharing, serve engine): ``batch["tokens"]`` holds only the divergent
        suffix of each prompt; ``prior`` is a per-stack list of
        ``(k_prior, v_prior)`` pairs (``[layers, B, T, H, d]``, dequantized
        shared pool pages) whose first ``prior_len[b]`` tokens the suffix
        attends through :func:`~repro.core.attention.prefix_suffix_attention`.
        For MLA stacks the prior is the latent stream itself
        (``(lat, None)`` from a shared_kv paged cache) and each layer expands
        it through its own up-projections (``mla.mla_prefill_cache``).
        Token positions (RoPE) are offset by ``prior_len`` so the suffix lands
        at its unshared global positions; the returned caches hold *suffix*
        content only and ``pos`` counts ``prior_len + lengths``.  Requires a
        token-only front (no vision / M-RoPE).  ``quant_impl`` selects the
        cache-quantization kernel (``kernels/kv_quant``).
        """
        cfg = self.cfg
        if prior is not None:
            if cfg.vision_stub or cfg.mrope_sections:
                raise ValueError(
                    "suffix prefill (prior=) requires a token-only front "
                    "(no vision/M-RoPE)"
                )
            if lengths is None or prior_len is None:
                raise ValueError("suffix prefill needs lengths and prior_len")
        x, positions = self._embed(params, batch)
        if prior is not None:
            positions = prior_len[:, None] + jnp.arange(
                x.shape[1], dtype=jnp.int32
            )[None]
        n_lead = cfg.n_patches if cfg.vision_stub else 0  # patch prefix offset
        cache_lengths = None if lengths is None else lengths + n_lead
        caches = []
        for i, (kind, _) in enumerate(self.stacks):
            if prior is None:
                def body(x, lp, _kind=kind):
                    x, cache = self._block_prefill(
                        lp, _kind, x, positions, max_seq, cache_lengths,
                        block_align, quant_impl=quant_impl,
                    )
                    return x, cache

                x, cache_stack = lax.scan(body, x, params[f"stack_{i}"])
            elif prior[i][1] is None:  # latent prior (MLA shared_kv pools)
                def body_l(x, xs, _kind=kind):
                    lp, kp = xs
                    x, cache = self._block_prefill(
                        lp, _kind, x, positions, max_seq, cache_lengths,
                        block_align, prior=(kp, None), prior_len=prior_len,
                        quant_impl=quant_impl,
                    )
                    return x, cache

                x, cache_stack = lax.scan(
                    body_l, x, (params[f"stack_{i}"], prior[i][0])
                )
            else:
                def body_p(x, xs, _kind=kind):
                    lp, kp, vp = xs
                    x, cache = self._block_prefill(
                        lp, _kind, x, positions, max_seq, cache_lengths,
                        block_align, prior=(kp, vp), prior_len=prior_len,
                        quant_impl=quant_impl,
                    )
                    return x, cache

                kp_i, vp_i = prior[i]
                x, cache_stack = lax.scan(
                    body_p, x, (params[f"stack_{i}"], kp_i, vp_i)
                )
            caches.append(cache_stack)
        if lengths is None:
            logits = self._logits(params, x[:, -1:])
            pos = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
        else:
            last = jnp.clip(n_lead + lengths - 1, 0, x.shape[1] - 1)
            x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
            logits = self._logits(params, x_last)
            pos = (n_lead + lengths).astype(jnp.int32)
            if prior_len is not None:
                pos = pos + prior_len.astype(jnp.int32)
        state = {"caches": caches, "pos": pos}
        return logits, state

    # ------------------------------------------------------------ decode

    def init_decode_state(self, batch_size: int, max_seq: int, *, mesh=None,
                          splitkv_axis: str = "data"):
        """Dense decode state.  When a ``mesh`` is given, the packed-block
        capacity is rounded up to the ``splitkv_axis`` size so
        ``dist.splitkv`` shards the block axis pad-free (mesh-aligned cache
        allocation — otherwise the per-call zero-pad copies the whole cache
        every decoded token at ``nb % axis_size != 0`` shapes)."""
        cfg = self.cfg
        align = qcache.splitkv_block_align(mesh, splitkv_axis)
        caches = []
        for kind, n in self.stacks:
            if cfg.mixer == "mla":
                one = mla.mla_init_cache(cfg, batch_size, max_seq, block_align=align)
            else:
                one = qcache.init_cache(
                    batch_size, cfg.n_kv_heads, cfg.head_dim, max_seq,
                    bits=cfg.kv_bits, block_n=cfg.kv_block, k_gran=cfg.kv_gran,
                    block_align=align,
                )
            caches.append(jax.tree.map(lambda a: jnp.broadcast_to(a, (n, *a.shape)), one))
        return {
            "caches": caches,
            "pos": jnp.zeros((batch_size,), jnp.int32),
        }

    def paged_spec(self) -> PagedSpec | None:
        """Declared cache family (see repro.models.family).  Plain attention
        and MLA both page; token-plus-patch fronts (VLM stub, M-RoPE) return
        ``None`` — the serving engine cannot feed their prefill."""
        cfg = self.cfg
        if cfg.vision_stub or cfg.mrope_sections:
            return None
        n_layers = sum(n for _, n in self.stacks)
        if cfg.mixer == "mla":
            return PagedSpec(
                paged=True, block_n=cfg.kv_block, n_kv_heads=1,
                d_k=cfg.kv_lora + cfg.qk_rope, d_v=cfg.kv_lora,
                shared_kv=True, page_layers=n_layers, supports_prior=True,
            )
        if cfg.mixer == "attn":
            return PagedSpec(
                paged=True, block_n=cfg.kv_block, n_kv_heads=cfg.n_kv_heads,
                d_k=cfg.head_dim, d_v=cfg.head_dim,
                page_layers=n_layers, supports_prior=True,
            )
        return None

    def init_paged_decode_state(self, batch_size: int, *, n_pages: int,
                                nb_max: int):
        """Paged decode state for the serving engine: per-stack
        :class:`~repro.core.qcache.PagedQuantKVCache` pools (stacked along
        layers, page tables managed host-side by serve/pages.py).  MLA stacks
        allocate the shared_kv latent pool layout
        (``mla.mla_init_paged_cache``); both families decode through
        ``kernels/paged_bitdecode``."""
        cfg = self.cfg
        spec = self.paged_spec()
        if spec is None or not spec.paged:
            raise ValueError(
                f"no paged decode path for mixer={cfg.mixer!r} with this "
                "front (see DecoderLM.paged_spec)"
            )
        caches = []
        for kind, n in self.stacks:
            if cfg.mixer == "mla":
                one = mla.mla_init_paged_cache(cfg, n_pages, batch_size, nb_max)
            else:
                one = qcache.init_paged_cache(
                    n_pages, batch_size, cfg.n_kv_heads, cfg.head_dim, nb_max,
                    bits=cfg.kv_bits, block_n=cfg.kv_block, k_gran=cfg.kv_gran,
                )
            caches.append(jax.tree.map(lambda a: jnp.broadcast_to(a, (n, *a.shape)), one))
        return {
            "caches": caches,
            "pos": jnp.zeros((batch_size,), jnp.int32),
        }

    def _block_decode(self, p, kind, x, positions, cache, impl, quant_impl):
        cfg = self.cfg
        h = layers.apply_norm(cfg.norm, p["ln1"], x, plus_one=cfg.rms_plus_one)
        if cfg.mixer == "mla":
            a, cache = mla.mla_decode(
                p["attn"], cfg, h, positions, cache, impl=impl,
                quant_impl=quant_impl,
            )
        else:
            a, cache = mattn.attn_decode(
                p["attn"], cfg, h, positions, cache, impl=impl,
                quant_impl=quant_impl,
            )
        if cfg.parallel_residual:
            f = layers.mlp(p["mlp"], h, cfg.act) if kind == "mlp" else 0.0
            return x + a + f, cache
        x = x + a
        if kind != "none":
            h2 = layers.apply_norm(cfg.norm, p["ln2"], x, plus_one=cfg.rms_plus_one)
            f = moe.moe_ffn(p["moe"], cfg, h2)[0] if kind == "moe" else layers.mlp(p["mlp"], h2, cfg.act)
            x = x + f
        return x, cache

    def decode_step(self, params, state, tokens, *, impl="auto", quant_impl="auto"):
        """tokens [B, 1] -> (logits [B,1,V], new state)."""
        cfg = self.cfg
        x = layers.embed(params["embed"], tokens)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        pos = state["pos"]
        if cfg.mrope_sections:
            positions = _mrope_decode_positions(cfg, pos)
        else:
            positions = pos[:, None]
        new_caches = []
        for i, (kind, _) in enumerate(self.stacks):
            def body(x, xs, _kind=kind):
                lp, cache = xs
                x, cache = self._block_decode(
                    lp, _kind, x, positions, cache, impl, quant_impl
                )
                return x, cache

            x, cache_stack = lax.scan(body, x, (params[f"stack_{i}"], state["caches"][i]))
            new_caches.append(cache_stack)
        logits = self._logits(params, x)
        return logits, {"caches": new_caches, "pos": pos + 1}


class HybridLM:
    """Zamba2-style hybrid: Mamba2 backbone + shared attention block.

    Layout: n_super super-blocks of (attn_every mamba layers + 1 invocation of
    the SHARED attention+MLP block), plus a tail of leftover mamba layers.
    The shared block has one set of weights but a separate KV cache per
    invocation — BitDecoding applies to those caches.
    """

    def __init__(self, cfg):
        self.cfg = cfg
        self.n_super = cfg.n_layers // cfg.attn_every
        self.tail = cfg.n_layers - self.n_super * cfg.attn_every

    def _mamba_def(self):
        cfg = self.cfg
        return {
            "ln": layers.norm_def(cfg.norm, cfg.d_model),
            "mixer": mamba2.mamba2_def(cfg),
        }

    def _shared_def(self):
        cfg = self.cfg
        return {
            "ln1": layers.norm_def(cfg.norm, cfg.d_model),
            "attn": mattn.attn_def(cfg),
            "ln2": layers.norm_def(cfg.norm, cfg.d_model),
            "mlp": layers.mlp_def(cfg.d_model, cfg.d_ff, cfg.act),
        }

    def param_defs(self):
        cfg = self.cfg
        defs = {
            "embed": layers.embed_def(cfg.padded_vocab, cfg.d_model),
            "final_norm": layers.norm_def(cfg.norm, cfg.d_model),
            "unembed": layers.unembed_def(cfg.d_model, cfg.padded_vocab),
            "shared_attn": self._shared_def(),
            "main": stack(stack(self._mamba_def(), cfg.attn_every, "inner"), self.n_super),
        }
        if self.tail:
            defs["tail"] = stack(self._mamba_def(), self.tail)
        return defs

    def init(self, rng):
        return init_tree(self.param_defs(), rng)

    def param_shapes(self):
        return shape_tree(self.param_defs())

    def param_specs(self, rules):
        return spec_tree(self.param_defs(), rules)

    def _mamba_train(self, p, x):
        cfg = self.cfg
        return x + mamba2.mamba2_train(
            p["mixer"], cfg, layers.apply_norm(cfg.norm, p["ln"], x)
        )

    def _shared_train(self, p, x, positions):
        cfg = self.cfg
        x = x + mattn.attn_train(
            p["attn"], cfg, layers.apply_norm(cfg.norm, p["ln1"], x), positions
        )
        return x + layers.mlp(p["mlp"], layers.apply_norm(cfg.norm, p["ln2"], x), cfg.act)

    def loss(self, params, batch):
        cfg = self.cfg
        x = layers.embed(params["embed"], batch["tokens"])
        positions = _positions_lm(*x.shape[:2])
        shared = params["shared_attn"]

        def super_body(x, group):
            def inner(x, lp):
                return self._mamba_train(lp, x), None

            x, _ = lax.scan(inner, x, group)
            x = self._shared_train(shared, x, positions)
            return x, None

        body = super_body
        if cfg.remat == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = lax.scan(body, x, params["main"])
        if self.tail:
            def tail_body(x, lp):
                return self._mamba_train(lp, x), None
            x, _ = lax.scan(tail_body, x, params["tail"])
        x = layers.apply_norm(cfg.norm, params["final_norm"], x)
        logits = layers.unembed(params["unembed"], x, cfg.vocab)
        return _ce_loss(logits[:, :-1], batch["labels"][:, 1:], batch["loss_mask"][:, 1:])

    def paged_spec(self) -> PagedSpec:
        """Mixed cache family: the shared attention block's caches (one per
        super-block invocation) page; the Mamba2 recurrent states are
        constant-size per-slot ``side_state`` the engine splices at admission
        and that carry no page-table work (asserted by the jaxpr proof in
        tests/test_serve_families.py).  ``exact_prefill``: the recurrent
        states would absorb right-padding, so prompts prefill at exact
        lengths; prefix sharing would additionally need prefix SSM states
        cached per page, which pages don't hold — ``supports_prior=False``."""
        cfg = self.cfg
        side = (("ssm_main", 2),) + ((("ssm_tail", 1),) if self.tail else ())
        return PagedSpec(
            paged=True, block_n=cfg.kv_block, n_kv_heads=cfg.n_kv_heads,
            d_k=cfg.head_dim, d_v=cfg.head_dim, page_layers=self.n_super,
            side_state=side, exact_prefill=True, supports_prior=False,
        )

    def _side_states(self, batch_size: int):
        cfg = self.cfg
        one_m = mamba2.mamba2_init_state(cfg, batch_size)
        st = {
            "ssm_main": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.n_super, cfg.attn_every, *a.shape)), one_m
            ),
        }
        if self.tail:
            st["ssm_tail"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.tail, *a.shape)), one_m
            )
        return st

    def init_decode_state(self, batch_size: int, max_seq: int, *, mesh=None,
                          splitkv_axis: str = "data"):
        cfg = self.cfg
        cache = qcache.init_cache(
            batch_size, cfg.n_kv_heads, cfg.head_dim, max_seq,
            bits=cfg.kv_bits, block_n=cfg.kv_block, k_gran=cfg.kv_gran,
            block_align=qcache.splitkv_block_align(mesh, splitkv_axis),
        )
        return {
            **self._side_states(batch_size),
            "caches": [jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.n_super, *a.shape)), cache
            )],
            "pos": jnp.zeros((batch_size,), jnp.int32),
        }

    def init_paged_decode_state(self, batch_size: int, *, n_pages: int,
                                nb_max: int):
        """Paged decode state: one PagedQuantKVCache pool set stacked over
        the ``n_super`` shared-attention invocations; SSM recurrent states
        stay dense per slot (they never touch the page table)."""
        cfg = self.cfg
        one = qcache.init_paged_cache(
            n_pages, batch_size, cfg.n_kv_heads, cfg.head_dim, nb_max,
            bits=cfg.kv_bits, block_n=cfg.kv_block, k_gran=cfg.kv_gran,
        )
        return {
            **self._side_states(batch_size),
            "caches": [jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.n_super, *a.shape)), one
            )],
            "pos": jnp.zeros((batch_size,), jnp.int32),
        }

    def decode_step(self, params, state, tokens, *, impl="auto", quant_impl="auto"):
        cfg = self.cfg
        x = layers.embed(params["embed"], tokens)
        pos = state["pos"]
        positions = pos[:, None]
        shared = params["shared_attn"]

        def super_body(x, xs):
            group, sst, cache = xs

            def inner(x, ys):
                lp, st = ys
                h = layers.apply_norm(cfg.norm, lp["ln"], x)
                out, st = mamba2.mamba2_decode(lp["mixer"], cfg, h, st)
                return x + out, st

            x, sst = lax.scan(inner, x, (group, sst))
            h = layers.apply_norm(cfg.norm, shared["ln1"], x)
            a, cache = mattn.attn_decode(
                shared["attn"], cfg, h, positions, cache, impl=impl,
                quant_impl=quant_impl,
            )
            x = x + a
            x = x + layers.mlp(
                shared["mlp"], layers.apply_norm(cfg.norm, shared["ln2"], x), cfg.act
            )
            return x, (sst, cache)

        x, (ssm_main, caches) = lax.scan(
            super_body, x, (params["main"], state["ssm_main"], state["caches"][0])
        )
        new_state = dict(state, ssm_main=ssm_main, caches=[caches], pos=pos + 1)
        if self.tail:
            def tail_body(x, ys):
                lp, st = ys
                h = layers.apply_norm(cfg.norm, lp["ln"], x)
                out, st = mamba2.mamba2_decode(lp["mixer"], cfg, h, st)
                return x + out, st

            x, ssm_tail = lax.scan(tail_body, x, (params["tail"], state["ssm_tail"]))
            new_state["ssm_tail"] = ssm_tail
        x = layers.apply_norm(cfg.norm, params["final_norm"], x)
        logits = layers.unembed(params["unembed"], x, cfg.vocab)
        return logits, new_state

    def prefill(self, params, batch, max_seq: int):
        """Chunked-parallel prefill: SSD scan for Mamba states, flash prefill
        + fused quantization for the shared attention caches."""
        cfg = self.cfg
        x = layers.embed(params["embed"], batch["tokens"])
        b, s = x.shape[:2]
        positions = _positions_lm(b, s)
        shared = params["shared_attn"]

        def super_body(x, group):
            def inner(x, lp):
                h = layers.apply_norm(cfg.norm, lp["ln"], x)
                out, st = mamba2.mamba2_prefill(lp["mixer"], cfg, h)
                return x + out, st

            x, states = lax.scan(inner, x, group)
            h = layers.apply_norm(cfg.norm, shared["ln1"], x)
            a, cache = mattn.attn_prefill_cache(shared["attn"], cfg, h, positions, max_seq)
            x = x + a
            x = x + layers.mlp(
                shared["mlp"], layers.apply_norm(cfg.norm, shared["ln2"], x), cfg.act
            )
            return x, (states, cache)

        x, (ssm_main, caches) = lax.scan(super_body, x, params["main"])
        state = {
            "ssm_main": ssm_main,
            "caches": [caches],
            "pos": jnp.full((b,), s, jnp.int32),
        }
        if self.tail:
            def tail_body(x, lp):
                h = layers.apply_norm(cfg.norm, lp["ln"], x)
                out, st = mamba2.mamba2_prefill(lp["mixer"], cfg, h)
                return x + out, st

            x, ssm_tail = lax.scan(tail_body, x, params["tail"])
            state["ssm_tail"] = ssm_tail
        x = layers.apply_norm(cfg.norm, params["final_norm"], x[:, -1:])
        logits = layers.unembed(params["unembed"], x, cfg.vocab)
        return logits, state


class XLSTMLM:
    """xLSTM: super-blocks of (mlstm_per_slstm mLSTM + 1 sLSTM) blocks."""

    def __init__(self, cfg):
        self.cfg = cfg
        per = cfg.mlstm_per_slstm + 1
        assert cfg.n_layers % per == 0, "n_layers must divide super-block size"
        self.n_super = cfg.n_layers // per

    def _mlstm_def(self):
        cfg = self.cfg
        return {"ln": layers.norm_def(cfg.norm, cfg.d_model), "mixer": xlstm.mlstm_def(cfg)}

    def _slstm_def(self):
        cfg = self.cfg
        return {"ln": layers.norm_def(cfg.norm, cfg.d_model), "mixer": xlstm.slstm_def(cfg)}

    def param_defs(self):
        cfg = self.cfg
        super_def = {
            "mlstm": stack(self._mlstm_def(), cfg.mlstm_per_slstm, "inner"),
            "slstm": self._slstm_def(),
        }
        return {
            "embed": layers.embed_def(cfg.padded_vocab, cfg.d_model),
            "final_norm": layers.norm_def(cfg.norm, cfg.d_model),
            "unembed": layers.unembed_def(cfg.d_model, cfg.padded_vocab),
            "blocks": stack(super_def, self.n_super),
        }

    def init(self, rng):
        return init_tree(self.param_defs(), rng)

    def param_shapes(self):
        return shape_tree(self.param_defs())

    def param_specs(self, rules):
        return spec_tree(self.param_defs(), rules)

    def _forward(self, params, x, states=None):
        """states=None -> training (fresh states, discarded)."""
        cfg = self.cfg
        carry_states = states is not None

        def super_body(x, xs):
            if carry_states:
                group, st = xs
            else:
                group, st = xs, None

            def inner(x, ys):
                if carry_states:
                    lp, s = ys
                else:
                    lp, s = ys, None
                h = layers.apply_norm(cfg.norm, lp["ln"], x)
                out, s = xlstm.mlstm_block(lp["mixer"], cfg, h, s)
                return x + out, s

            if carry_states:
                x, mst = lax.scan(inner, x, (group["mlstm"], st["mlstm"]))
            else:
                x, mst = lax.scan(inner, x, group["mlstm"])
            h = layers.apply_norm(cfg.norm, group["slstm"]["ln"], x)
            out, sst = xlstm.slstm_block(
                group["slstm"]["mixer"], cfg, h, st["slstm"] if carry_states else None
            )
            x = x + out
            return x, {"mlstm": mst, "slstm": sst}

        body = super_body
        if cfg.remat == "full" and not carry_states:
            body = jax.checkpoint(body, prevent_cse=False)
        if carry_states:
            x, new_states = lax.scan(body, x, (params["blocks"], states))
        else:
            x, new_states = lax.scan(body, x, params["blocks"])
        return x, new_states

    def loss(self, params, batch):
        cfg = self.cfg
        x = layers.embed(params["embed"], batch["tokens"])
        x, _ = self._forward(params, x)
        x = layers.apply_norm(cfg.norm, params["final_norm"], x)
        logits = layers.unembed(params["unembed"], x, cfg.vocab)
        return _ce_loss(logits[:, :-1], batch["labels"][:, 1:], batch["loss_mask"][:, 1:])

    def paged_spec(self) -> PagedSpec:
        """No growing KV anywhere: every state is a constant-size recurrent
        pytree.  ``paged=False`` routes the serving engine's exact-length
        shim; ``side_state`` tells it where the recurrent states live and on
        which axis their batch sits (after the super-block stacking dims)."""
        return PagedSpec(
            paged=False, block_n=self.cfg.kv_block, n_kv_heads=0, d_k=0,
            d_v=0, side_state=(("blocks/mlstm", 2), ("blocks/slstm", 1)),
            exact_prefill=True,
        )

    def init_decode_state(self, batch_size: int, max_seq: int = 0):
        cfg = self.cfg
        m1 = xlstm.mlstm_init_state(cfg, batch_size)
        s1 = xlstm.slstm_init_state(cfg, batch_size)
        return {
            "blocks": {
                "mlstm": jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (self.n_super, cfg.mlstm_per_slstm, *a.shape)), m1
                ),
                "slstm": jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (self.n_super, *a.shape)), s1
                ),
            },
            "pos": jnp.zeros((batch_size,), jnp.int32),
        }

    def decode_step(self, params, state, tokens, *, impl="auto", quant_impl="auto"):
        del impl, quant_impl  # no attention KV cache in this backbone
        x = layers.embed(params["embed"], tokens)
        x, new_states = self._forward(params, x, state["blocks"])
        x = layers.apply_norm(self.cfg.norm, params["final_norm"], x)
        logits = layers.unembed(params["unembed"], x, self.cfg.vocab)
        return logits, {"blocks": new_states, "pos": state["pos"] + 1}

    def prefill(self, params, batch, max_seq: int = 0):
        x = layers.embed(params["embed"], batch["tokens"])
        state = self.init_decode_state(x.shape[0])
        x, new_states = self._forward(params, x, state["blocks"])
        x = layers.apply_norm(self.cfg.norm, params["final_norm"], x[:, -1:])
        logits = layers.unembed(params["unembed"], x, self.cfg.vocab)
        pos = jnp.full((x.shape[0],), batch["tokens"].shape[1], jnp.int32)
        return logits, {"blocks": new_states, "pos": pos}
