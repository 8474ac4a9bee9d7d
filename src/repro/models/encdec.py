"""Encoder-decoder LM (Seamless-M4T backbone).  The modality frontend is a
stub: the encoder consumes precomputed frame embeddings from input_specs().

Decode uses two BitDecoding caches per decoder layer, both paged under
identity page tables (``qcache.to_pages``):
  * self-attention: growing quantized cache (online Residual-Kernel path);
  * cross-attention: *static* quantized cache built once after encoding —
    the paper's offline (Fig. 1a) case, same kernels, residual never flushed.
``prefill`` returns them as the dense caches prefill writes; hand its state
to ``qcache.to_pages`` before decoding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import qcache
from repro.models import attention as mattn
from repro.models import layers
from repro.models.params import init_tree, shape_tree, spec_tree, stack
from repro.models.transformer import _ce_loss, _positions_lm


class EncDecLM:
    def __init__(self, cfg):
        self.cfg = cfg

    def paged_spec(self):
        """Not serveable by the engine: prefill needs encoder frame
        embeddings, which Requests don't carry (repro.models.family)."""
        return None

    def _enc_def(self):
        cfg = self.cfg
        return {
            "ln1": layers.norm_def(cfg.norm, cfg.d_model),
            "attn": mattn.attn_def(cfg),
            "ln2": layers.norm_def(cfg.norm, cfg.d_model),
            "mlp": layers.mlp_def(cfg.d_model, cfg.d_ff, cfg.act, cfg.attn_bias),
        }

    def _dec_def(self):
        d = self._enc_def()
        cfg = self.cfg
        d["ln_x"] = layers.norm_def(cfg.norm, cfg.d_model)
        d["xattn"] = mattn.cross_attn_def(cfg)
        return d

    def param_defs(self):
        cfg = self.cfg
        return {
            "embed": layers.embed_def(cfg.padded_vocab, cfg.d_model),
            "enc_norm": layers.norm_def(cfg.norm, cfg.d_model),
            "final_norm": layers.norm_def(cfg.norm, cfg.d_model),
            "unembed": layers.unembed_def(cfg.d_model, cfg.padded_vocab),
            "encoder": stack(self._enc_def(), cfg.enc_layers),
            "decoder": stack(self._dec_def(), cfg.dec_layers),
        }

    def init(self, rng):
        return init_tree(self.param_defs(), rng)

    def param_shapes(self):
        return shape_tree(self.param_defs())

    def param_specs(self, rules):
        return spec_tree(self.param_defs(), rules)

    # ------------------------------------------------------------ encoder

    def encode(self, params, frames):
        """frames [B, T, d] (stub frontend output) -> memory [B, T, d]."""
        cfg = self.cfg
        x = frames.astype(jnp.bfloat16)
        positions = _positions_lm(*x.shape[:2])

        def body(x, lp):
            h = layers.apply_norm(cfg.norm, lp["ln1"], x)
            x = x + mattn.attn_train(lp["attn"], cfg, h, positions, causal=False)
            h2 = layers.apply_norm(cfg.norm, lp["ln2"], x)
            return x + layers.mlp(lp["mlp"], h2, cfg.act), None

        if cfg.remat == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = lax.scan(body, x, params["encoder"])
        return layers.apply_norm(cfg.norm, params["enc_norm"], x)

    # ------------------------------------------------------------ train

    def loss(self, params, batch):
        cfg = self.cfg
        mem = self.encode(params, batch["frames"])
        x = layers.embed(params["embed"], batch["tokens"])
        positions = _positions_lm(*x.shape[:2])

        def body(x, lp):
            h = layers.apply_norm(cfg.norm, lp["ln1"], x)
            x = x + mattn.attn_train(lp["attn"], cfg, h, positions)
            hx = layers.apply_norm(cfg.norm, lp["ln_x"], x)
            x = x + mattn.cross_attn_train(lp["xattn"], cfg, hx, mem)
            h2 = layers.apply_norm(cfg.norm, lp["ln2"], x)
            return x + layers.mlp(lp["mlp"], h2, cfg.act), None

        if cfg.remat == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = lax.scan(body, x, params["decoder"])
        x = layers.apply_norm(cfg.norm, params["final_norm"], x)
        logits = layers.unembed(params["unembed"], x, cfg.vocab)
        return _ce_loss(logits[:, :-1], batch["labels"][:, 1:], batch["loss_mask"][:, 1:])

    # ------------------------------------------------------------ decode

    def init_decode_state(self, batch_size: int, max_seq: int):
        cfg = self.cfg
        self_c = qcache.init_cache(
            batch_size, cfg.n_kv_heads, cfg.head_dim, max_seq,
            bits=cfg.kv_bits, block_n=cfg.kv_block, k_gran=cfg.kv_gran,
        )
        cross_c = qcache.init_cache(
            batch_size, cfg.n_kv_heads, cfg.head_dim, cfg.enc_len,
            bits=cfg.kv_bits, block_n=cfg.kv_block, k_gran=cfg.kv_gran,
        )
        n = cfg.dec_layers
        return qcache.to_pages({
            "self": jax.tree.map(lambda a: jnp.broadcast_to(a, (n, *a.shape)), self_c),
            "cross": jax.tree.map(lambda a: jnp.broadcast_to(a, (n, *a.shape)), cross_c),
            "pos": jnp.zeros((batch_size,), jnp.int32),
        })

    def prefill(self, params, batch, max_seq: int):
        """Encode + build static cross caches + prefill decoder self caches."""
        cfg = self.cfg
        mem = self.encode(params, batch["frames"])
        x = layers.embed(params["embed"], batch["tokens"])
        b, s = x.shape[:2]
        positions = _positions_lm(b, s)

        def body(x, lp):
            h = layers.apply_norm(cfg.norm, lp["ln1"], x)
            a, self_c = mattn.attn_prefill_cache(lp["attn"], cfg, h, positions, max_seq)
            x = x + a
            cross_c = mattn.build_cross_cache(lp["xattn"], cfg, mem)
            hx = layers.apply_norm(cfg.norm, lp["ln_x"], x)
            x = x + mattn.cross_attn_train(lp["xattn"], cfg, hx, mem)
            h2 = layers.apply_norm(cfg.norm, lp["ln2"], x)
            return x + layers.mlp(lp["mlp"], h2, cfg.act), (self_c, cross_c)

        x, (self_caches, cross_caches) = lax.scan(body, x, params["decoder"])
        x = layers.apply_norm(cfg.norm, params["final_norm"], x[:, -1:])
        logits = layers.unembed(params["unembed"], x, cfg.vocab)
        return logits, {
            "self": self_caches,
            "cross": cross_caches,
            "pos": jnp.full((b,), s, jnp.int32),
        }

    def decode_step(self, params, state, tokens, *, impl="auto", quant_impl="auto"):
        cfg = self.cfg
        x = layers.embed(params["embed"], tokens)
        pos = state["pos"]
        positions = pos[:, None]

        def body(x, xs):
            lp, self_c, cross_c = xs
            h = layers.apply_norm(cfg.norm, lp["ln1"], x)
            a, self_c = mattn.attn_decode(
                lp["attn"], cfg, h, positions, self_c, impl=impl,
                quant_impl=quant_impl,
            )
            x = x + a
            hx = layers.apply_norm(cfg.norm, lp["ln_x"], x)
            x = x + mattn.cross_attn_decode(lp["xattn"], cfg, hx, cross_c, impl=impl)
            h2 = layers.apply_norm(cfg.norm, lp["ln2"], x)
            return x + layers.mlp(lp["mlp"], h2, cfg.act), self_c

        x, self_caches = lax.scan(
            body, x, (params["decoder"], state["self"], state["cross"])
        )
        x = layers.apply_norm(cfg.norm, params["final_norm"], x)
        logits = layers.unembed(params["unembed"], x, cfg.vocab)
        return logits, dict(state, self=self_caches, pos=pos + 1)
