"""Self-speculative decoding on the hierarchical quantized cache (QuantSpec).

The paper's cache *is* a draft/verify hierarchy: a low-bit committed cache
plus a bf16 residual window, behind one page table and one weight set.  This
module adds the two device-side passes that exploit it:

* **draft** (:func:`make_draft_fn`): decode ``spec_k - 1`` tokens greedily
  against an aggressive read path — the same packed pools dequantized at a
  truncated ``spec_bits`` bit-width (``core.attention.use_draft``), appends
  residual-only into a throwaway copy of the decode state.  No second model,
  no second page table, no pool writes.
* **verify** (:func:`make_verify_fn`): one jitted scan of full-fidelity
  decode steps over the whole ``[B, spec_k]`` feed matrix (the committed +
  residual path every non-speculative cycle uses), with per-lane alive masks
  (``core.attention.masked_append``) freezing a lane's cache, ``pos``, and
  recurrent side-state the moment its draft diverges.

Acceptance rule (host side, serve/engine.py): the engine is greedy, so a
draft token is accepted iff it *equals* the verify argmax at its position —
the longest matching prefix is accepted and the first divergence is replaced
by the verify token (which is always kept: the cycle emits >= 1 token per
live lane).  Because accepted tokens are exact matches and masked appends on
live lanes are bitwise identical to sequential appends, the emitted stream
and the cache contents equal non-speculative decode bit for bit — asserted
across cache families in tests/test_serve_spec.py.

Counters the engine maintains per cycle (see docs/SERVING.md §11):
``spec_cycles``, ``spec_draft_tokens``, ``spec_accepted_tokens``,
``spec_rejected_tokens`` — and per request ``spec_accepted`` /
``spec_rejected``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import attention as catt
from repro.core import qcache
from repro.models.family import get_path, set_path


def _mask_leaf(alive, new, old, bdim: int):
    """Select per-lane between new/old on the leaf's batch axis ``bdim``."""
    sel = alive.reshape((1,) * bdim + (-1,) + (1,) * (new.ndim - bdim - 1))
    return jnp.where(sel, new, old)


def _freeze_dead_lanes(st_new: dict, st_old: dict, alive, side_state) -> dict:
    """Return ``st_new`` with ``pos`` and every declared recurrent side-state
    path masked back to ``st_old`` on dead lanes.  Cache appends are already
    masked in-line by ``masked_append``; this covers the state the model
    updates unconditionally (position counter, SSM/xLSTM recurrent states)."""
    st_new = dict(st_new)
    st_new["pos"] = jnp.where(alive, st_new["pos"], st_old["pos"])
    for path, bdim in side_state:
        merged = jax.tree.map(
            lambda n, o: _mask_leaf(alive, n, o, bdim),
            get_path(st_new, path), get_path(st_old, path),
        )
        set_path(st_new, path, merged)
    return st_new


def make_draft_fn(model, *, spec_k: int, spec_bits: int,
                  quant_impl: str = "auto"):
    """Build the jitted draft pass.

    Returns ``draft(params, state, tok0)`` with ``tok0`` int32 ``[B]`` (the
    token each lane is about to feed this cycle) producing int32
    ``[B, spec_k - 1]`` candidate continuations.  The state is widened
    (``qcache.widen_residual``) so up to ``spec_k - 1`` residual-only appends
    stay in bounds, then discarded — the committed pools are never written.
    Lanes that aren't decoding produce garbage drafts the engine ignores.
    """
    steps = spec_k - 1
    if steps < 1:
        raise ValueError(f"spec_k={spec_k} needs no draft pass (k >= 2)")

    def serve_spec_draft(params, state, tok0):
        st = dict(state)
        if "caches" in st:
            st["caches"] = [qcache.widen_residual(c, steps) for c in st["caches"]]

        def body(carry, _):
            st, tok = carry
            with catt.use_draft(spec_bits):
                logits, st = model.decode_step(
                    params, st, tok[:, None], impl="auto",
                    quant_impl=quant_impl,
                )
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (st, nxt), nxt

        _, toks = lax.scan(body, (st, tok0), None, length=steps)
        return jnp.moveaxis(toks, 0, 1)  # [B, steps]

    return jax.jit(serve_spec_draft)


def make_verify_fn(model, spec, *, impl: str = "auto",
                   quant_impl: str = "auto"):
    """Build the jitted multi-token verify pass for one cache family.

    ``spec`` is the model's :class:`~repro.models.family.PagedSpec` (or
    ``None``) — only its ``side_state`` declaration is used, so every family
    the engine serves (attn, MLA latent, hybrid SSM, recurrent shim) verifies
    through this one function.

    Returns ``verify(params, state, feeds, limit, forced)``:

    * ``feeds`` int32 ``[B, K]`` — token to feed at each scan step (column 0
      is the cycle's committed feed; columns ``1..`` are draft candidates or,
      on replay lanes, teacher-forced history);
    * ``limit`` int32 ``[B]`` — feeds available per lane (0 = idle slot);
    * ``forced`` bool ``[B]`` — replay lanes accept unconditionally
      (preemption-by-rematerialization teacher forcing, SERVING.md §10).

    Producing ``(v, applied, finite, new_state)``: ``v[b, i]`` is the verify
    argmax after feeding ``feeds[b, i]``; ``applied[b, i]`` whether that feed
    actually ran (lane still alive); ``finite[b, i]`` whether the logits row
    was fully finite (step-level fault isolation joins the acceptance rule
    host-side).  A lane dies at step ``i + 1`` unless it is forced or
    ``v[b, i] == feeds[b, i + 1]`` — the greedy exact-match acceptance rule.
    Dead lanes touch nothing: cache appends are masked, ``pos`` and recurrent
    side-state restored, so the surviving state is bitwise the sequential one.
    """
    side = tuple(spec.side_state) if spec is not None else ()

    def serve_spec_verify(params, state, feeds, limit, forced):
        k = feeds.shape[1]
        feeds_t = jnp.moveaxis(feeds, 0, 1)  # [K, B]
        nxt_t = jnp.moveaxis(
            jnp.concatenate([feeds[:, 1:], feeds[:, :1]], axis=1), 0, 1
        )
        idx = jnp.arange(k, dtype=jnp.int32)
        alive0 = limit > 0

        def body(carry, xs):
            st, alive = carry
            tok, nxt, i = xs
            with catt.masked_append(alive):
                logits, st2 = model.decode_step(
                    params, st, tok[:, None], impl=impl, quant_impl=quant_impl
                )
            row = logits[:, 0].astype(jnp.float32)
            v = jnp.argmax(row, axis=-1).astype(jnp.int32)
            finite = jnp.all(jnp.isfinite(row), axis=-1)
            st2 = _freeze_dead_lanes(st2, st, alive, side)
            alive_next = alive & ((i + 1) < limit) & (forced | (v == nxt))
            return (st2, alive_next), (v, alive, finite)

        (st, _), (v, applied, finite) = lax.scan(
            body, (state, alive0), (feeds_t, nxt_t, idx)
        )
        return (
            jnp.moveaxis(v, 0, 1),        # [B, K]
            jnp.moveaxis(applied, 0, 1),  # [B, K]
            jnp.moveaxis(finite, 0, 1),   # [B, K]
            st,
        )

    return jax.jit(serve_spec_verify)
