"""Quantized KV cache with half-precision residual buffer (paper §IV-A(2), §V-B).

The cache partitions the sequence  X = X_pack ∪ X_res  (paper Eq. before (1)):
packed low-bit blocks of ``block_n`` tokens plus a bf16 residual tail of
capacity ``N_r = block_n`` — the TPU tile-aligned instantiation of the paper's
``N_r = P_n × W_n × R``.  Newly decoded tokens append to the residual; when it
fills, the whole block is quantized+packed+committed in one fused pass (the
Residual Kernel, kernels/residual_flush) and the residual restarts.  The
flush is gated behind ``lax.cond`` so the other ``block_n - 1`` decode steps
do no quantization work.  ``shared_kv=True`` stores a single latent stream
(MLA mode) — no V-side fields.

Two at-rest layouts share this data model: the dense :class:`QuantKVCache`
(``[B, H, nb, ...]``, one private block range per sequence) and the paged
:class:`PagedQuantKVCache` (shared ``[P, H, ...]`` page pools walked through
per-sequence page tables — the serving engine's layout, allocated by
serve/pages.py).  Both append paths run the same gated fused flush.

See docs/ARCHITECTURE.md for the packed ``(words, scale, zero)`` layout spec.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import layout, quantizer
from repro.kernels.kv_quant import ops as kvq_ops
from repro.kernels.residual_flush import ops as rf_ops


@dataclasses.dataclass
class QuantKVCache:
    # packed low-bit cache + metadata ("half2" scale/zero pairs)
    kw: jax.Array          # int32 [B, H, nb, npr, d_k]
    k_scale: jax.Array
    k_zero: jax.Array
    vw: jax.Array | None   # int32 [B, H, nb, npr, d_v]; None when shared_kv
    v_scale: jax.Array | None
    v_zero: jax.Array | None
    # half-precision residual cache
    k_res: jax.Array       # bf16 [B, H, block_n, d_k]
    v_res: jax.Array | None
    # occupancy
    pack_blocks: jax.Array  # int32 [B]
    res_len: jax.Array      # int32 [B]
    # static config
    bits: int
    block_n: int
    k_gran: str
    shared_kv: bool

    @property
    def length(self) -> jax.Array:
        return self.pack_blocks * self.block_n + self.res_len

    @property
    def capacity(self) -> int:
        return (self.kw.shape[2] + 1) * self.block_n


jax.tree_util.register_dataclass(
    QuantKVCache,
    data_fields=[
        "kw", "k_scale", "k_zero", "vw", "v_scale", "v_zero",
        "k_res", "v_res", "pack_blocks", "res_len",
    ],
    meta_fields=["bits", "block_n", "k_gran", "shared_kv"],
)


def init_cache(
    batch: int,
    h_kv: int,
    d_k: int,
    max_seq: int,
    *,
    d_v: int | None = None,
    bits: int = 4,
    block_n: int = 128,
    k_gran: str = "channel",
    shared_kv: bool = False,
    param_dtype=jnp.bfloat16,
    res_dtype=jnp.bfloat16,
    block_align: int | None = None,
) -> QuantKVCache:
    """Allocate an empty cache with capacity >= max_seq tokens.

    ``block_align`` rounds the packed block count ``nb`` up to a multiple
    (normally the split-KV mesh-axis size, plumbed through
    ``model.init_decode_state(..., mesh=...)``) so ``dist.splitkv`` shards the
    block axis without its per-call zero-pad — which is otherwise a full
    cache copy every decoded token when ``nb % axis_size != 0``.
    """
    nb = max(1, -(-max_seq // block_n))
    if block_align and block_align > 1:
        nb = -(-nb // block_align) * block_align
    npr = layout.words_per_block(block_n, bits)
    if k_gran == "channel":
        kp_shape = (batch, h_kv, nb, d_k)
    else:
        kp_shape = (batch, h_kv, nb, block_n)
    z32 = lambda s: jnp.zeros(s, jnp.int32)  # noqa: E731
    zp = lambda s: jnp.zeros(s, param_dtype)  # noqa: E731
    if shared_kv:
        vw = v_scale = v_zero = v_res = None
    else:
        d_v = d_v if d_v is not None else d_k
        vw = z32((batch, h_kv, nb, npr, d_v))
        v_scale = zp((batch, h_kv, nb, block_n))
        v_zero = zp((batch, h_kv, nb, block_n))
        v_res = jnp.zeros((batch, h_kv, block_n, d_v), res_dtype)
    return QuantKVCache(
        kw=z32((batch, h_kv, nb, npr, d_k)),
        k_scale=zp(kp_shape),
        k_zero=zp(kp_shape),
        vw=vw, v_scale=v_scale, v_zero=v_zero,
        k_res=jnp.zeros((batch, h_kv, block_n, d_k), res_dtype),
        v_res=v_res,
        pack_blocks=z32((batch,)),
        res_len=z32((batch,)),
        bits=bits, block_n=block_n, k_gran=k_gran, shared_kv=shared_kv,
    )


def _append_residual(cache: QuantKVCache, k_new, v_new, mask=None):
    """Write one new token per sequence into the residual buffers.  Returns
    (k_res, v_res, res_len_after, full) — the shared front half of both
    append paths.

    ``mask`` ([B] bool, optional) freezes sequences: a ``False`` lane keeps
    its residual rows and ``res_len`` bitwise unchanged (``jnp.where`` with a
    true predicate returns the written array unchanged, so masked appends on
    live lanes are bitwise identical to unmasked ones).  This is the
    multi-token verify primitive for self-speculative decoding: lanes whose
    draft already diverged stop appending mid-scan.
    """

    def write(res, rl, new):
        return lax.dynamic_update_slice(res, new.astype(res.dtype), (0, rl, 0))

    k_res = jax.vmap(write)(cache.k_res, cache.res_len, k_new)
    v_res = None if cache.shared_kv else jax.vmap(write)(
        cache.v_res, cache.res_len, v_new
    )
    if mask is None:
        rl = cache.res_len + 1
    else:
        sel = mask[:, None, None, None]
        k_res = jnp.where(sel, k_res, cache.k_res)
        if v_res is not None:
            v_res = jnp.where(sel, v_res, cache.v_res)
        rl = cache.res_len + mask.astype(jnp.int32)
    return k_res, v_res, rl, rl == cache.block_n


def _commit_append(cache: QuantKVCache, packed, k_res, v_res, full, rl):
    """Shared back half of both append paths: write the (possibly flushed)
    packed arrays and update occupancy.  ``packed`` is the six packed fields
    in dataclass order (V side None when shared_kv)."""
    kw, ks, kz, vw, vs, vz = packed
    return dataclasses.replace(
        cache, kw=kw, k_scale=ks, k_zero=kz, vw=vw, v_scale=vs, v_zero=vz,
        k_res=k_res, v_res=v_res,
        pack_blocks=jnp.where(full, cache.pack_blocks + 1, cache.pack_blocks),
        res_len=jnp.where(full, 0, rl),
    )


def append_decode(
    cache: QuantKVCache,
    k_new: jax.Array,  # [B, H, 1, d_k]
    v_new: jax.Array | None,  # [B, H, 1, d_v]; None when shared_kv
    *,
    quant_impl: str = "auto",
    mask=None,
) -> QuantKVCache:
    """Append one decoded token per sequence; flush the residual block when
    full (paper: "Once per token generation, the Residual Kernel ... optionally
    quantizes it (when res_len = N_r) into packed format").

    The flush is *gated*: the fused residual-flush kernel
    (kernels/residual_flush) runs under a ``lax.cond`` taken only when some
    sequence's residual just filled — 1 step in ``block_n``.  On the other
    ``block_n - 1`` steps the hot path is exactly one token-row write into
    the bf16 residual plus the occupancy update; no quantization, packing,
    or packed-cache traffic at all (previously the whole residual block was
    re-quantized speculatively every token — kept as
    :func:`append_decode_speculative` for benchmarking).

    quant_impl: 'auto' | 'pallas' | 'xla', forwarded to
    ``residual_flush.ops.residual_flush``.

    ``mask`` ([B] bool, optional): lanes with ``mask=False`` keep the cache
    bitwise unchanged (no residual write, no occupancy change; a concurrent
    flush of *other* lanes selects the frozen lane's old block back — the
    same non-full select the gated flush always performs).  See
    :func:`_append_residual`.
    """
    k_res, v_res, rl, full = _append_residual(cache, k_new, v_new, mask)

    if cache.shared_kv:
        packed = (cache.kw, cache.k_scale, cache.k_zero)
    else:
        packed = (cache.kw, cache.k_scale, cache.k_zero,
                  cache.vw, cache.v_scale, cache.v_zero)

    def flush(p):
        if cache.shared_kv:
            kw, ks, kz = p
            vw = vs = vz = None
        else:
            kw, ks, kz, vw, vs, vz = p
        out = rf_ops.residual_flush(
            kw, ks, kz, vw, vs, vz, k_res, v_res,
            full.astype(jnp.int32), cache.pack_blocks,
            bits=cache.bits, block_n=cache.block_n, k_gran=cache.k_gran,
            shared_kv=cache.shared_kv, impl=quant_impl,
        )
        return out[:3] if cache.shared_kv else out

    packed = lax.cond(jnp.any(full), flush, lambda p: p, packed)
    if cache.shared_kv:
        packed = (*packed, None, None, None)
    return _commit_append(cache, packed, k_res, v_res, full, rl)


def append_decode_speculative(
    cache: QuantKVCache,
    k_new: jax.Array,  # [B, H, 1, d_k]
    v_new: jax.Array | None,  # [B, H, 1, d_v]; None when shared_kv
    *,
    quant_impl: str = "xla",
) -> QuantKVCache:
    """Pre-fusion append path: the flush op runs *unconditionally* on every
    decoded token (no ``lax.cond`` gate), re-quantizing the whole residual
    block and select-committing at block granularity each step.  Kept as the
    baseline for bench_quant_overhead's flush-vs-speculative sweep and as a
    second oracle for the gated path — identical cache contents by
    construction, since both call the same flush op and a non-full sequence
    selects its old block back."""
    k_res, v_res, rl, full = _append_residual(cache, k_new, v_new)
    packed = rf_ops.residual_flush(
        cache.kw, cache.k_scale, cache.k_zero,
        cache.vw, cache.v_scale, cache.v_zero,
        k_res, v_res, full.astype(jnp.int32), cache.pack_blocks,
        bits=cache.bits, block_n=cache.block_n, k_gran=cache.k_gran,
        shared_kv=cache.shared_kv, impl=quant_impl,
    )
    return _commit_append(cache, packed, k_res, v_res, full, rl)


def splitkv_block_align(mesh, axis: str | None) -> int | None:
    """Block-axis alignment implied by a split-KV mesh axis (None when no
    mesh / unknown axis) — the ``block_align`` to pass to :func:`init_cache`
    so ``dist.splitkv`` never zero-pads the packed-block axis per call."""
    if mesh is None or axis is None or axis not in mesh.axis_names:
        return None
    return int(mesh.shape[axis])


def prefill(
    cache: QuantKVCache,
    k: jax.Array,  # [B, H, L, d_k]
    v: jax.Array | None,
    *,
    lengths: jax.Array | None = None,
    quant_impl: str = "auto",
) -> QuantKVCache:
    """Fill the cache from a prefill of static length L: quantize the first
    L - (L mod N_r) tokens into packed blocks, keep the tail in the residual
    (paper §V-B(1)).

    ``lengths`` ([B] int32, optional) marks ragged batches — same-bucket
    prompts right-padded to a common L (the serve scheduler's bucketed
    prefill).  Per sequence ``b``, only ``lengths[b] // block_n`` packed
    blocks are valid and the residual holds tokens
    ``[lengths[b] - lengths[b] % block_n, lengths[b])``; blocks beyond
    ``pack_blocks[b]`` contain pad-polluted stats but are never read (the
    same invariant decode already relies on), and the next decode flush
    overwrites them.  Quantization is per-block, so valid blocks are bitwise
    identical to an exact-length prefill of the same prompt.
    """
    b, h, L, d_k = k.shape
    block_n = cache.block_n
    n_full = L // block_n
    res = L - n_full * block_n
    updates = _quantize_full_region(cache, k, v, n_full, quant_impl)
    if lengths is not None:
        # ragged tail: residual rows come from each sequence's own block
        # boundary (which may sit inside the padded batch's packed region)
        lo = ((lengths // block_n) * block_n).astype(jnp.int32)
        idx = jnp.minimum(
            lo[:, None] + jnp.arange(block_n, dtype=jnp.int32), L - 1
        )  # [B, block_n]; rows >= res_len[b] are unread garbage

        def tail(x, res_buf):
            g = jnp.take_along_axis(x, idx[:, None, :, None], axis=2)
            return g.astype(res_buf.dtype)

        updates["k_res"] = tail(k, cache.k_res)
        if not cache.shared_kv:
            updates["v_res"] = tail(v, cache.v_res)
        updates["pack_blocks"] = (lengths // block_n).astype(jnp.int32)
        updates["res_len"] = (lengths % block_n).astype(jnp.int32)
        return dataclasses.replace(cache, **updates)
    if res:
        kr = jnp.zeros_like(cache.k_res)
        kr = lax.dynamic_update_slice(
            kr, k[:, :, n_full * block_n :].astype(kr.dtype), (0, 0, 0, 0))
        updates["k_res"] = kr
        if not cache.shared_kv:
            vr = jnp.zeros_like(cache.v_res)
            vr = lax.dynamic_update_slice(
                vr, v[:, :, n_full * block_n :].astype(vr.dtype), (0, 0, 0, 0))
            updates["v_res"] = vr
    updates["pack_blocks"] = jnp.full((b,), n_full, jnp.int32)
    updates["res_len"] = jnp.full((b,), res, jnp.int32)
    return dataclasses.replace(cache, **updates)


# --------------------------------------------------------------------------
# Paged cache (vLLM-style page pools + per-sequence block tables)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class PagedQuantKVCache:
    """Paged twin of :class:`QuantKVCache`: the packed blocks of all
    sequences live in shared *page pools* (``[P, H, ...]``, one pool entry =
    one ``block_n``-token block) and each sequence walks its blocks through a
    ``page_table`` row.  The bf16 residual tail stays dense per slot — only
    committed blocks are paged.

    Invariants (serve/pages.py is the allocator that maintains them):

    * pool pages ``[0, B)`` are per-slot scratch, never allocated to a
      request; ``page_table`` entries that don't (yet) hold an allocated page
      equal the slot index, so a flush through a stale/idle entry lands in
      the slot's own scratch page and destinations stay pairwise distinct;
    * ``page_table[b, j]`` holds the pool page of sequence ``b``'s packed
      block ``j`` for all ``j < pack_blocks[b]``, and the page for block
      ``pack_blocks[b]`` is allocated *before* the decode step whose flush
      commits it;
    * ``length = pack_blocks * block_n + res_len`` exactly as in the dense
      cache.

    ``shared_kv=True`` (the MLA latent mode) pages a *single* quantized
    latent stream: the V-side pools and residual are ``None`` and the decode
    kernel slices V out of the dequantized K tile, exactly as the dense
    shared mode does (kernels/paged_bitdecode).
    """

    # shared page pools
    kw: jax.Array           # int32 [P, H, npr, d_k]
    k_scale: jax.Array      # [P, H, d_k] (channel) | [P, H, block_n] (tensor)
    k_zero: jax.Array
    vw: jax.Array | None    # int32 [P, H, npr, d_v]; None when shared_kv
    v_scale: jax.Array | None  # [P, H, block_n]
    v_zero: jax.Array | None
    # dense per-slot residual tail
    k_res: jax.Array        # bf16 [B, H, block_n, d_k]
    v_res: jax.Array | None
    # per-sequence block table + occupancy
    page_table: jax.Array   # int32 [B, nb_max]
    pack_blocks: jax.Array  # int32 [B]
    res_len: jax.Array      # int32 [B]
    # static config
    bits: int
    block_n: int
    k_gran: str
    shared_kv: bool = False

    @property
    def length(self) -> jax.Array:
        return self.pack_blocks * self.block_n + self.res_len

    @property
    def n_pages(self) -> int:
        return self.kw.shape[0]


jax.tree_util.register_dataclass(
    PagedQuantKVCache,
    data_fields=[
        "kw", "k_scale", "k_zero", "vw", "v_scale", "v_zero",
        "k_res", "v_res", "page_table", "pack_blocks", "res_len",
    ],
    meta_fields=["bits", "block_n", "k_gran", "shared_kv"],
)


def init_paged_cache(
    n_pages: int,
    batch: int,
    h_kv: int,
    d_k: int,
    nb_max: int,
    *,
    d_v: int | None = None,
    bits: int = 4,
    block_n: int = 128,
    k_gran: str = "channel",
    shared_kv: bool = False,
    param_dtype=jnp.bfloat16,
    res_dtype=jnp.bfloat16,
) -> PagedQuantKVCache:
    """Allocate empty page pools for ``batch`` decode slots.

    ``n_pages`` must be ``> batch``: the first ``batch`` pages are the
    per-slot scratch pages required by the flush-destination injectivity
    contract.  ``nb_max`` is the page-table width (max packed blocks any one
    sequence can hold).  The fresh ``page_table`` points every entry at the
    owning slot's scratch page.  ``shared_kv=True`` allocates the MLA latent
    layout: a single K-side pool set, no V pools/residual.
    """
    if n_pages <= batch:
        raise ValueError(
            f"n_pages={n_pages} must exceed batch={batch} (the first "
            "`batch` pages are reserved per-slot scratch)"
        )
    npr = layout.words_per_block(block_n, bits)
    kp_shape = (n_pages, h_kv, d_k) if k_gran == "channel" else (n_pages, h_kv, block_n)
    z32 = lambda s: jnp.zeros(s, jnp.int32)  # noqa: E731
    zp = lambda s: jnp.zeros(s, param_dtype)  # noqa: E731
    table = jnp.broadcast_to(
        jnp.arange(batch, dtype=jnp.int32)[:, None], (batch, nb_max)
    )
    if shared_kv:
        vw = v_scale = v_zero = v_res = None
    else:
        d_v = d_v if d_v is not None else d_k
        vw = z32((n_pages, h_kv, npr, d_v))
        v_scale = zp((n_pages, h_kv, block_n))
        v_zero = zp((n_pages, h_kv, block_n))
        v_res = jnp.zeros((batch, h_kv, block_n, d_v), res_dtype)
    return PagedQuantKVCache(
        kw=z32((n_pages, h_kv, npr, d_k)),
        k_scale=zp(kp_shape),
        k_zero=zp(kp_shape),
        vw=vw, v_scale=v_scale, v_zero=v_zero,
        k_res=jnp.zeros((batch, h_kv, block_n, d_k), res_dtype),
        v_res=v_res,
        page_table=table,
        pack_blocks=z32((batch,)),
        res_len=z32((batch,)),
        bits=bits, block_n=block_n, k_gran=k_gran, shared_kv=shared_kv,
    )


def paged_append_decode(
    cache: PagedQuantKVCache,
    k_new: jax.Array,  # [B, H, 1, d_k]
    v_new: jax.Array | None,  # [B, H, 1, d_v]; None when shared_kv
    *,
    quant_impl: str = "auto",
    mask=None,
    flush_op=rf_ops.paged_residual_flush,
) -> PagedQuantKVCache:
    """Paged per-token append: write the new token row into the dense
    residual, and — gated behind ``lax.cond`` exactly like the dense
    :func:`append_decode` — commit just-filled residual blocks *through the
    page table* into the pools with the fused paged residual-flush kernel.
    Non-flush steps do zero quantize/pack/pool work.

    The flush destination per sequence is ``page_table[b, pack_blocks[b]]``
    when its residual filled, else the slot's scratch page ``b`` (keeps the
    kernel's destination set pairwise distinct; see PagedQuantKVCache's
    invariants).

    ``mask`` ([B] bool, optional): frozen lanes (``mask=False``) keep
    residual, occupancy, and their pool pages bitwise unchanged — a frozen
    lane is never ``full``, so any concurrent flush routes its destination to
    the lane's own scratch page (the standard non-flushing destination).

    ``flush_op`` is the flush (``residual_flush.ops.paged_residual_flush``'s
    signature); pools on a mesh pass ``dist.splitkv.splitkv_paged_flush``.
    """
    b = cache.k_res.shape[0]
    nb_max = cache.page_table.shape[1]
    k_res, v_res, rl, full = _append_residual(cache, k_new, v_new, mask)

    blk = jnp.clip(cache.pack_blocks, 0, nb_max - 1)
    dest = jnp.take_along_axis(cache.page_table, blk[:, None], axis=1)[:, 0]
    dest = jnp.where(full, dest, jnp.arange(b, dtype=jnp.int32))
    dest = jnp.clip(dest, 0, cache.n_pages - 1)

    if cache.shared_kv:
        pools = (cache.kw, cache.k_scale, cache.k_zero)
    else:
        pools = (cache.kw, cache.k_scale, cache.k_zero,
                 cache.vw, cache.v_scale, cache.v_zero)

    def flush(p):
        if cache.shared_kv:
            kw, ks, kz = p
            vw = vs = vz = None
        else:
            kw, ks, kz, vw, vs, vz = p
        out = flush_op(
            kw, ks, kz, vw, vs, vz, k_res, v_res,
            full.astype(jnp.int32), dest,
            bits=cache.bits, block_n=cache.block_n, k_gran=cache.k_gran,
            shared_kv=cache.shared_kv, impl=quant_impl,
        )
        return out[:3] if cache.shared_kv else out

    pools = lax.cond(jnp.any(full), flush, lambda p: p, pools)
    if cache.shared_kv:
        kw, ks, kz = pools
        vw = vs = vz = None
    else:
        kw, ks, kz, vw, vs, vz = pools
    return dataclasses.replace(
        cache, kw=kw, k_scale=ks, k_zero=kz, vw=vw, v_scale=vs, v_zero=vz,
        k_res=k_res, v_res=v_res,
        pack_blocks=jnp.where(full, cache.pack_blocks + 1, cache.pack_blocks),
        res_len=jnp.where(full, 0, rl),
    )


# --------------------------------------------------------------------------
# Speculative-draft residual helpers (QuantSpec-style self-speculation)
# --------------------------------------------------------------------------


def widen_residual(cache, extra: int):
    """Pad the residual token axis by ``extra`` rows (zeros).

    The speculative *draft* pass appends up to ``spec_k - 1`` tokens without
    ever flushing (the packed pools are read-only to the draft — its state is
    discarded after the verify step).  Widening the residual keeps those
    appends in-bounds when ``res_len`` starts near ``block_n``; the decode
    references read the residual capacity from ``k_res.shape[2]`` and mask by
    ``res_len``, so a wider residual changes nothing numerically.  Works on
    dense and paged caches, including layer-stacked serving state.
    """
    if extra <= 0:
        return cache

    def pad(res):
        cfg = [(0, 0)] * res.ndim
        cfg[-2] = (0, extra)
        return jnp.pad(res, cfg)

    upd = {"k_res": pad(cache.k_res)}
    if cache.v_res is not None:
        upd["v_res"] = pad(cache.v_res)
    return dataclasses.replace(cache, **upd)


def draft_append(cache, k_new, v_new):
    """Residual-only append for the speculative draft pass: write the new
    token row and bump ``res_len`` — no flush, no pool/packed-cache traffic,
    no ``pack_blocks`` change.  The caller guarantees capacity via
    :func:`widen_residual`; draft state is discarded after verification, so
    committed blocks are never touched.  Dense and paged caches alike.
    """
    k_res, v_res, rl, _ = _append_residual(cache, k_new, v_new)
    return dataclasses.replace(cache, k_res=k_res, v_res=v_res, res_len=rl)


# Pool fields of the paged cache, in dataclass order, with the rank each has
# before any model-stacking dims are prepended (the serving engine stacks a
# leading layer axis; serve/pages.py indexes pages at axis 1 accordingly).
_PAGED_POOL_FIELDS = ("kw", "k_scale", "k_zero", "vw", "v_scale", "v_zero")
_PAGED_POOL_BASE_RANK = {
    "kw": 4, "k_scale": 3, "k_zero": 3, "vw": 4, "v_scale": 3, "v_zero": 3,
}


def _page_axis(arr, field: str) -> int:
    """Page-pool axis of a (possibly layer-stacked) pool field."""
    return arr.ndim - _PAGED_POOL_BASE_RANK[field]


def copy_pages(
    cache: PagedQuantKVCache,
    src: jax.Array,  # int32 [N]
    dst: jax.Array,  # int32 [N], pairwise distinct, disjoint from src
) -> PagedQuantKVCache:
    """Device-side pool-page copy — the copy-on-write primitive.

    Every ``dst[i]`` page becomes a bitwise replica of ``src[i]`` across all
    six pool fields (packed words + scale/zero metadata, K and V sides).
    Works on layer-stacked caches (the serving engine's state) as well as the
    base layout: the page axis is located from each field's base rank, so the
    copy moves the page across every stacked layer in one gather+scatter.

    The serving engine calls this when a decode flush is about to land in a
    page with refcount > 1 (serve/engine.py): the request gets a private
    replica and only its own page-table column is repointed, so other
    requests sharing the original page never observe the write.  The copy is
    deliberately unconditional on what the subsequent write touches — today's
    only COW site (the residual flush) overwrites the whole block, but the
    replica contract keeps COW correct for any future partial writer
    (preemption re-materialization, partial-block adoption).
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    upd = {}
    for f in _PAGED_POOL_FIELDS:
        pool = getattr(cache, f)
        if pool is None:  # shared_kv latent layout has no V-side pools
            continue
        moved = jnp.moveaxis(pool, _page_axis(pool, f), 0)
        moved = moved.at[dst].set(moved[src])
        upd[f] = jnp.moveaxis(moved, 0, _page_axis(pool, f))
    return dataclasses.replace(cache, **upd)


def dequant_prior(
    cache: PagedQuantKVCache,
    pages: jax.Array,  # int32 [B, J] pool pages (rows right-padded; garbage
    #                    columns are masked by the caller via prior_len)
):
    """Gather pool pages and dequantize them into raw bf16 prior K/V for the
    shared-prefix suffix prefill.

    Returns ``(k, v)`` shaped ``[*lead, B, J*block_n, H, d]`` (lead = the
    cache's stacking dims, e.g. the layer axis) in natural token order —
    the layout :func:`repro.core.attention.prefix_suffix_attention` takes as
    ``k_prior``/``v_prior``.  Pool K is stored post-RoPE, so the dequantized
    prior needs no position re-application; the numeric contract is that
    suffix tokens see the shared prefix exactly as decode attention would
    (dequantized), which is the same approximation the paper's decode path
    already makes.

    ``shared_kv`` caches (the MLA latent pools) return ``(latent, None)``:
    there is no V-side pool, and the per-head K/V views are derived from the
    latent by the model's own up-projections
    (``repro.models.mla.mla_prefill_cache`` with ``prior=``).
    """
    pages = jnp.asarray(pages, jnp.int32)

    def gather(field: str):
        arr = getattr(cache, field)
        return jnp.moveaxis(arr, _page_axis(arr, field), 0)[pages]

    def dq(words, scale, zero, gran: str):
        # words [B, J, *lead, H, npr, d] -> [B, J, *lead, H, block_n, d];
        # one shared dequant path with the kernels' oracles, so prefix
        # sharing can never diverge numerically from decode attention
        return quantizer.unpack_and_dequantize(
            words, scale, zero, cache.bits, gran, dtype=jnp.bfloat16
        )

    k = dq(gather("kw"), gather("k_scale"), gather("k_zero"), cache.k_gran)
    v = None if cache.shared_kv else dq(
        gather("vw"), gather("v_scale"), gather("v_zero"), "tensor"
    )

    def to_prior(x):
        # [B, J, *lead, H, n, d] -> [*lead, B, J*n, H, d]
        b, j = x.shape[0], x.shape[1]
        h, n, d = x.shape[-3], x.shape[-2], x.shape[-1]
        lead = x.shape[2:-3]
        perm = (
            tuple(range(2, 2 + len(lead)))  # lead dims first
            + (0, 1, x.ndim - 2, x.ndim - 3, x.ndim - 1)  # B, J, n, H, d
        )
        x = jnp.transpose(x, perm)
        return x.reshape(*lead, b, j * n, h, d).astype(jnp.bfloat16)

    return to_prior(k), (None if v is None else to_prior(v))


def _quantize_full_region(cache, k, v, n_full: int, quant_impl: str) -> dict:
    """Quantize+pack the first ``n_full`` blocks of a prefill into updates for
    the packed fields (shared front of the uniform and ragged prefill paths)."""
    block_n = cache.block_n
    updates: dict = {}
    if not n_full:
        return updates
    w, s, z = kvq_ops.quantize_kv(
        k[:, :, : n_full * block_n], cache.bits, cache.k_gran,
        block_n=block_n, param_dtype=cache.k_scale.dtype, impl=quant_impl,
    )
    updates["kw"] = lax.dynamic_update_slice(cache.kw, w, (0, 0, 0, 0, 0))
    updates["k_scale"] = lax.dynamic_update_slice(cache.k_scale, s, (0, 0, 0, 0))
    updates["k_zero"] = lax.dynamic_update_slice(cache.k_zero, z, (0, 0, 0, 0))
    if not cache.shared_kv:
        wv, sv, zv = kvq_ops.quantize_kv(
            v[:, :, : n_full * block_n], cache.bits, "tensor",
            block_n=block_n, param_dtype=cache.k_scale.dtype, impl=quant_impl,
        )
        updates["vw"] = lax.dynamic_update_slice(cache.vw, wv, (0, 0, 0, 0, 0))
        updates["v_scale"] = lax.dynamic_update_slice(cache.v_scale, sv, (0, 0, 0, 0))
        updates["v_zero"] = lax.dynamic_update_slice(cache.v_zero, zv, (0, 0, 0, 0))
    return updates
