"""Attention entry points: query transformation, decode dispatch, blockwise
prefill attention.

Query transformation (paper §V-A): during decode the query tensor is
``[B, 1, h_q, d]``; a naive QK^T is a GEMV that underfills the MXU.  We
reshape to ``[B, h_kv, g_q, d]`` (``g_q = h_q / h_kv``) so the grouped query
heads that share a KV head become the M dimension of a real matmul — MHA
(g_q = 1), GQA (g_q > 1) and MQA (h_kv = 1) all flow through the same kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import qcache
from repro.core.qcache import PagedQuantKVCache, QuantKVCache
from repro.kernels.bitdecode import ops as bd_ops
from repro.kernels.paged_bitdecode import ops as pg_ops

MASK_VALUE = -1e37


def query_transform(q: jax.Array, h_kv: int) -> jax.Array:
    """[B, 1, h_q, d] -> [B, h_kv, g_q, d].  Head h shares KV head h // g_q."""
    b, s1, h_q, d = q.shape
    if s1 != 1:
        raise ValueError(f"decode expects q_len=1, got {s1}")
    if h_q % h_kv:
        raise ValueError(f"h_q={h_q} not divisible by h_kv={h_kv}")
    return q.reshape(b, h_kv, h_q // h_kv, d)


def inverse_query_transform(o: jax.Array) -> jax.Array:
    """[B, h_kv, g_q, d_v] -> [B, 1, h_q, d_v]."""
    b, h_kv, g_q, d_v = o.shape
    return o.reshape(b, 1, h_kv * g_q, d_v)


# Split-KV (sequence-parallel) decode context: when set, decode_attention
# routes through dist.splitkv with the packed cache sharded along blocks.
# page_affine additionally declares the pools' leading (page) axis sharded
# along the same mesh axis (page-affine allocator — serve/pages.py), so the
# walk reads each page only on the chip that stores it.
_SPLITKV: dict = {"mesh": None, "axis": "data", "page_affine": False}


class use_splitkv:
    """Context manager enabling cross-chip split-KV decode (long-context,
    small-batch shapes).  Used by the launcher/dry-run around lowering."""

    def __init__(self, mesh, axis: str = "data", *, page_affine: bool = False):
        self.mesh, self.axis = mesh, axis
        self.page_affine = page_affine

    def __enter__(self):
        self._prev = dict(_SPLITKV)
        _SPLITKV["mesh"], _SPLITKV["axis"] = self.mesh, self.axis
        _SPLITKV["page_affine"] = self.page_affine
        return self

    def __exit__(self, *exc):
        _SPLITKV.update(self._prev)
        return False


# Speculative-decode contexts (trace-time, same pattern as _SPLITKV).  Both
# take effect inside :func:`decode_append_attention` / :func:`decode_attention`
# so the model code (models/attention.py, models/mla.py, transformer stacks)
# needs no signature changes to participate in draft/verify cycles.
_SPEC: dict = {"mask": None, "draft_bits": None}


class masked_append:
    """Freeze a subset of batch lanes during cache appends (the multi-token
    *verify* scan of self-speculative decoding).

    ``mask`` is a traced ``[B]`` bool array from the enclosing jit scope:
    lanes with ``mask=False`` keep their cache bitwise unchanged while live
    lanes append exactly as an unmasked step would (``qcache`` masks with
    ``jnp.where``, which is the identity on true lanes).  Only cache appends
    are masked — the caller masks ``pos`` and recurrent side-state itself.
    """

    def __init__(self, mask):
        self.mask = mask

    def __enter__(self):
        self._prev = _SPEC["mask"]
        _SPEC["mask"] = self.mask
        return self

    def __exit__(self, *exc):
        _SPEC["mask"] = self._prev
        return False


class use_draft:
    """Switch decode attention to the speculative *draft* read path: the
    packed cache is dequantized at ``bits`` (truncated-bit read, see
    ``kernels/bitdecode/ref._dequant_blocks``) and appends are residual-only
    (``qcache.draft_append`` — no flush, pools untouched).  Draft state is
    discarded after the verify step, so the committed cache is read-only
    here.  Forces the XLA reference kernels and bypasses split-KV routing.
    """

    def __init__(self, bits: int):
        self.bits = int(bits)

    def __enter__(self):
        self._prev = _SPEC["draft_bits"]
        _SPEC["draft_bits"] = self.bits
        return self

    def __exit__(self, *exc):
        _SPEC["draft_bits"] = self._prev
        return False


def decode_attention(
    q: jax.Array,  # [B, 1, h_q, d_k]
    cache: QuantKVCache,
    *,
    sm_scale: float | None = None,
    d_v: int | None = None,
    impl: str = "auto",
    num_splits: int | str | None = "auto",
    return_lse: bool = False,
):
    """Low-bit fused decode attention against a QuantKVCache.

    Split-KV decode is two-level:

    * **in-kernel** (``num_splits``): the packed-block walk becomes an extra
      parallel grid dimension with per-split (o, lse) partials and a fused
      logsumexp merge.  ``"auto"`` applies the heuristic in
      ``kernels/bitdecode/ops.auto_num_splits``: split only when ``B x H_kv``
      underfills the chip's parallel grid slots (the single-batch
      long-context regime — e.g. B=1, H_kv=2 at 128K) AND the sequence is
      long enough that each split owns >= 2 packed blocks; batch-heavy
      serving shapes keep ``num_splits = 1`` and pay nothing.
    * **cross-chip** (:class:`use_splitkv`): the packed cache is sharded
      along a mesh axis and per-chip partials merge with the same lse math
      (repro.dist.splitkv).  Both levels compose.

    ``cache`` may be a dense :class:`QuantKVCache` or a paged
    :class:`PagedQuantKVCache` (serving engine layout): the paged route runs
    ``kernels/paged_bitdecode`` over the cache's page table, with the same
    two split-KV levels (in-kernel ``num_splits``; cross-chip page-table-walk
    sharding via ``dist.splitkv.splitkv_paged_decode_attention``).
    """
    if isinstance(cache, PagedQuantKVCache):
        return _paged_decode_attention(
            q, cache, sm_scale=sm_scale, d_v=d_v, impl=impl,
            num_splits=num_splits, return_lse=return_lse,
        )
    draft_bits = _SPEC["draft_bits"]
    if draft_bits is None and _SPLITKV["mesh"] is not None and not return_lse:
        from repro.dist import splitkv as _sk

        return _sk.splitkv_decode_attention(
            q, cache, _SPLITKV["mesh"], axis=_SPLITKV["axis"],
            sm_scale=sm_scale, d_v=d_v, impl=impl, num_splits=num_splits,
        )
    h_kv = cache.kw.shape[1]
    qt = query_transform(q, h_kv)
    out = bd_ops.bitdecode_attention(
        qt, cache.kw, cache.k_scale, cache.k_zero,
        cache.vw, cache.v_scale, cache.v_zero,
        cache.k_res, cache.v_res, cache.pack_blocks, cache.res_len,
        bits=cache.bits, block_n=cache.block_n, sm_scale=sm_scale,
        k_gran=cache.k_gran, shared_kv=cache.shared_kv, d_v=d_v,
        impl=impl, num_splits=num_splits, return_lse=return_lse,
        draft_bits=draft_bits,
    )
    if return_lse:
        o, lse = out
        return inverse_query_transform(o), lse
    return inverse_query_transform(out)


def _paged_decode_attention(
    q: jax.Array,  # [B, 1, h_q, d_k]
    cache: PagedQuantKVCache,
    *,
    sm_scale: float | None,
    d_v: int | None,
    impl: str,
    num_splits,
    return_lse: bool,
):
    """Paged decode dispatch: page-table walk through kernels/paged_bitdecode
    (or, under :class:`use_splitkv`, the table walk sharded across chips).
    ``d_v`` is required for shared_kv (MLA latent) caches — the V width is a
    channel slice of the latent, not a stored pool dimension."""
    draft_bits = _SPEC["draft_bits"]
    if draft_bits is None and _SPLITKV["mesh"] is not None and not return_lse:
        from repro.dist import splitkv as _sk

        return _sk.splitkv_paged_decode_attention(
            q, cache, _SPLITKV["mesh"], axis=_SPLITKV["axis"],
            sm_scale=sm_scale, d_v=d_v, impl=impl, num_splits=num_splits,
            page_affine=_SPLITKV["page_affine"],
        )
    h_kv = cache.kw.shape[1]
    qt = query_transform(q, h_kv)
    out = pg_ops.paged_bitdecode_attention(
        qt, cache.kw, cache.k_scale, cache.k_zero,
        cache.vw, cache.v_scale, cache.v_zero,
        cache.k_res, cache.v_res,
        cache.page_table, cache.pack_blocks, cache.res_len,
        bits=cache.bits, block_n=cache.block_n, sm_scale=sm_scale,
        k_gran=cache.k_gran, shared_kv=cache.shared_kv, d_v=d_v,
        impl=impl, num_splits=num_splits, return_lse=return_lse,
        draft_bits=draft_bits,
    )
    if return_lse:
        o, lse = out
        return inverse_query_transform(o), lse
    return inverse_query_transform(out)


def decode_append_attention(
    q: jax.Array,  # [B, 1, h_q, d_k]
    cache: QuantKVCache | PagedQuantKVCache,
    k_new: jax.Array,  # [B, H, 1, d_k]
    v_new: jax.Array | None,  # None when shared_kv
    *,
    quant_impl: str = "auto",
    **attn_kwargs,
):
    """The per-token serving hot path in one call: append the new KV token to
    the cache (residual write + gated residual-flush kernel, see
    ``qcache.append_decode`` / ``qcache.paged_append_decode``) and run fused
    low-bit decode attention over the updated cache.  Returns
    ``(out, cache)``.

    ``quant_impl`` selects the flush implementation
    ('auto' | 'pallas' | 'xla'); ``attn_kwargs`` are forwarded to
    :func:`decode_attention` (``impl``, ``num_splits``, ``sm_scale``,
    ``d_v``, ...).  Model blocks (models/attention.py, models/mla.py) route
    through here so the engine's impl switches reach both kernels, and the
    dense/paged choice follows the cache type — the serving engine swaps the
    decode state for a paged one and the model code never changes.

    Under :class:`use_splitkv` the paged flush runs per chip as well
    (``dist.splitkv.splitkv_paged_flush``).

    The speculative contexts hook in here: under :class:`use_draft` the
    append is residual-only (``qcache.draft_append``) and the attention read
    dequantizes at the truncated draft bit-width; under :class:`masked_append`
    frozen lanes skip the append bitwise (multi-token verify).
    """
    if _SPEC["draft_bits"] is not None:
        cache = qcache.draft_append(cache, k_new, v_new)
    elif isinstance(cache, PagedQuantKVCache):
        flush = {}
        if _SPLITKV["mesh"] is not None:
            from repro.dist import splitkv as _sk

            flush["flush_op"] = functools.partial(
                _sk.splitkv_paged_flush, mesh=_SPLITKV["mesh"],
                axis=_SPLITKV["axis"], page_affine=_SPLITKV["page_affine"],
            )
        cache = qcache.paged_append_decode(
            cache, k_new, v_new, quant_impl=quant_impl, mask=_SPEC["mask"],
            **flush,
        )
    else:
        cache = qcache.append_decode(
            cache, k_new, v_new, quant_impl=quant_impl, mask=_SPEC["mask"]
        )
    return decode_attention(q, cache, **attn_kwargs), cache


def prefix_suffix_attention(
    q: jax.Array,        # [B, S, h_q, d_k]  suffix queries
    k: jax.Array,        # [B, S, h_kv, d_k] suffix keys
    v: jax.Array,        # [B, S, h_kv, d_v] suffix values
    k_prior: jax.Array,  # [B, T, h_kv, d_k] shared-prefix keys (right-padded)
    v_prior: jax.Array,  # [B, T, h_kv, d_v]
    prior_len: jax.Array,  # [B] int32 — valid prior tokens per sequence
    *,
    sm_scale: float | None = None,
) -> jax.Array:
    """Causal attention for a prompt *suffix* against a materialized prefix.

    The shared-prefix prefill path (serve engine → ``DecoderLM.prefill`` with
    ``prior=``) computes fresh Q/K/V only for the divergent suffix tokens;
    their attention must still cover the shared leading blocks, which arrive
    here as dequantized pool pages (``qcache.dequant_prior``).  Suffix query
    row ``j`` (global position ``prior_len[b] + j``) attends prior columns
    ``< prior_len[b]`` plus suffix columns ``<= j`` — exactly the rows
    ``[prior_len, prior_len + S)`` of full causal attention over the
    concatenated sequence, so with a *raw* prior this is bitwise the tail of
    :func:`blockwise_attention` (asserted in tests/test_serve_prefix.py).

    Ragged prior: rows are right-padded to a common ``T`` and masked by
    ``prior_len`` — mixed share counts batch into one call.  Pure-jnp with an
    O(S·(T+S)) score tile; prefill-rate bound at serving bucket sizes
    (a flash_prefill suffix mode is the ROADMAP residue).
    """
    b, s, h_q, d_k = q.shape
    t = k_prior.shape[1]
    h_kv = k.shape[2]
    g = h_q // h_kv
    d_v = v.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / (d_k**0.5)
    qg = q.reshape(b, s, h_kv, g, d_k).astype(jnp.bfloat16)
    kcat = jnp.concatenate([k_prior, k], axis=1).astype(jnp.bfloat16)
    vcat = jnp.concatenate([v_prior, v], axis=1).astype(jnp.bfloat16)
    scores = (
        jnp.einsum(
            "bshgd,bthd->bhsgt", qg, kcat,
            preferred_element_type=jnp.float32,
        )
        * sm_scale
    )  # [B, h_kv, S, g, T+S]
    cols = jnp.arange(t + s, dtype=jnp.int32)
    rows = jnp.arange(s, dtype=jnp.int32)
    in_prior = (cols[None, None, :] < prior_len[:, None, None]) & (
        cols[None, None, :] < t
    )  # [B, 1, T+S]
    in_suffix = (cols[None, :] >= t) & (cols[None, :] - t <= rows[:, None])
    valid = in_prior | in_suffix[None]  # [B, S, T+S]
    scores = jnp.where(valid[:, None, :, None, :], scores, MASK_VALUE)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum(
        "bhsgt,bthd->bshgd", p.astype(jnp.bfloat16), vcat,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, s, h_q, d_v)


def blockwise_attention(
    q: jax.Array,  # [B, S, h_q, d_k]
    k: jax.Array,  # [B, T, h_kv, d_k]
    v: jax.Array,  # [B, T, h_kv, d_v]
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_k: int = 512,
    q_offset: int = 0,
    impl: str = "xla",
) -> jax.Array:
    """Memory-subquadratic (flash-style) attention in pure jnp.

    Scans KV blocks with online-softmax carries; never materializes the
    [S, T] score matrix.  Used for prefill/training; GQA handled by folding
    the query-group dimension (the training-time face of the paper's query
    transformation).  q_offset shifts query positions for cross-chunk decode.

    impl="pallas" routes through the fused flash_prefill kernel (forward
    only — the VMEM-resident path that removes the materialized-score HBM
    traffic measured in EXPERIMENTS §Perf cells B/C); requires q_offset=0,
    same q/kv lengths and d_k == d_v.
    """
    if impl == "pallas":
        from repro.kernels.flash_prefill import ops as fp_ops

        assert q_offset == 0 and q.shape[1] == k.shape[1]
        out = fp_ops.flash_prefill_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            sm_scale=sm_scale, causal=causal, impl="pallas",
        )
        return out.transpose(0, 2, 1, 3)
    b, s, h_q, d_k = q.shape
    _, t, h_kv, d_v = v.shape
    g = h_q // h_kv
    if sm_scale is None:
        sm_scale = 1.0 / (d_k**0.5)
    nb = -(-t // block_k)
    t_pad = nb * block_k
    if t_pad != t:
        k = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))

    qg = q.reshape(b, s, h_kv, g, d_k).astype(jnp.bfloat16)
    kb = k.reshape(b, nb, block_k, h_kv, d_k).astype(jnp.bfloat16)
    vb = v.reshape(b, nb, block_k, h_kv, d_v).astype(jnp.bfloat16)
    kb = jnp.moveaxis(kb, 1, 0)  # [nb, B, block_k, h_kv, d_k]
    vb = jnp.moveaxis(vb, 1, 0)

    rows = jnp.arange(s, dtype=jnp.int32) + q_offset  # global query positions

    def step(carry, blk):
        m, l, acc, j = carry
        kj, vj = blk
        cols = j * block_k + jnp.arange(block_k, dtype=jnp.int32)
        sblk = lax.dot_general(
            qg, kj, (((4,), (3,)), ((0, 2), (0, 2))),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [B, h_kv, S, g, block_k]
        valid = cols[None, :] < t
        if causal:
            valid = valid & (cols[None, :] <= rows[:, None])  # [S, block_k]
        else:
            valid = jnp.broadcast_to(valid, (s, block_k))
        sblk = jnp.where(valid[None, None, :, None, :], sblk, MASK_VALUE)
        m_new = jnp.maximum(m, jnp.max(sblk, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sblk - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # (§Perf iteration C2, REFUTED: storing p in bf16 to halve the tile
        # traffic added convert materializations and *increased* bytes 19% —
        # the f32 tile stays; see EXPERIMENTS.md)
        pv = lax.dot_general(
            p.astype(jnp.bfloat16), vj, (((4,), (1,)), ((0, 1), (0, 2))),
            preferred_element_type=jnp.float32,
        )  # [B, h_kv, S, g, d_v]
        acc_new = acc * alpha + pv
        return (m_new, l_new, acc_new, j + 1), None

    m0 = jnp.full((b, h_kv, s, g, 1), MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((b, h_kv, s, g, 1), jnp.float32)
    acc0 = jnp.zeros((b, h_kv, s, g, d_v), jnp.float32)
    (m, l, acc, _), _ = lax.scan(step, (m0, l0, acc0, jnp.int32(0)), (kb, vb))
    out = acc / l
    return out.transpose(0, 2, 1, 3, 4).reshape(b, s, h_q, d_v)
