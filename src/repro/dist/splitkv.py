"""Cross-chip split-KV decode: FlashDecoding partitioning across a mesh axis.

The single-chip kernel already splits the packed-block walk across its grid
(kernels/bitdecode, ``num_splits``); this module is the level above: the
packed cache is sharded *across chips* along the block axis of a mesh axis
(normally "data", which carries no batch at the long-context small-batch
shapes — see launch/mesh.pick_batch_axes), every chip runs the local fused
kernel over its shard, and the per-chip partials are combined with the
logsumexp merge specified by tests/test_splitkv_math.py:

    m = max_i lse_i;  w_i = exp(lse_i - m);  out = sum_i w_i o_i / sum_i w_i

A shard whose block range lies beyond ``pack_blocks[b]`` computes no valid
tokens; the kernel's finalize l=0 guard emits lse ~ -inf, so its weight
underflows to exactly 0 and the merge is unaffected.  The bf16 residual tail
is replicated and processed by the *last* shard only (it usually owns the
fewest valid blocks, so the extra block balances the walk).

Padding: this module shards dim 2 (the packed-block axis ``nb``) of every
packed cache field — ``kw [B, H, nb, npr, d]`` and the ``[B, H, nb, …]``
scale/zero arrays (layout spec: docs/ARCHITECTURE.md §2).  When
``nb % axis_size != 0`` the axis is zero-padded *per call* before the
shard_map; padded blocks sit beyond ``pack_blocks`` so they are never read
as valid, but the pad is a full-cache copy every decode step at that shape —
size caches so ``axis_size`` divides ``nb`` (ROADMAP: mesh-aligned cache
allocation).  Queries, residuals, and occupancy counters are replicated.

Mesh axes are *physical* names here (normally ``"data"``) — the logical-axis
indirection of dist.sharding applies to parameters, not to this explicitly
shard_mapped path.  The mesh is passed in explicitly.

Merge math and diagrams: docs/ARCHITECTURE.md §5.  Wired in through
:class:`repro.core.attention.use_splitkv`, which the launchers enter around
lowering the long-context decode cells and the serve engine enters for its
split-KV decode step.

Paged twin: :func:`splitkv_paged_decode_attention` shards the page-table
*walk* (not the pools) for PagedQuantKVCache states — see its docstring and
docs/ARCHITECTURE.md §7.  :func:`splitkv_paged_flush` runs the decode
flush on the same pools: Mosaic kernels are never partitioned by the
compiler, so every kernel of a step on a mesh runs under ``shard_map``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as PS

from repro.kernels.bitdecode import ops as bd_ops
from repro.kernels.paged_bitdecode import ops as pg_ops
from repro.kernels.residual_flush import ops as rf_ops


def merge_collective(o, lse, axis: str):
    """lse-merge of per-shard flash partials across mesh axis ``axis``.

    o: [..., g, d_v] normalized per-shard output; lse: [..., g].  Returns the
    merged output, replicated along ``axis``.
    """
    m = lax.pmax(lse, axis)
    w = jnp.exp(lse - m)
    num = lax.psum(w[..., None] * o, axis)
    den = lax.psum(w, axis)
    return num / jnp.maximum(den, 1e-30)[..., None]


def _pad_block_axis(x, pad: int):
    """Zero-pad the packed-block axis (dim 2 of [B, H, nb, ...]) so it splits
    evenly across the mesh axis.  Padded blocks sit beyond pack_blocks and
    are never read as valid.

    NB: when nb is not already a multiple of the axis size this copies the
    cache every call — size caches so nb divides the split axis (ROADMAP:
    mesh-aligned cache allocation)."""
    if not pad or x is None:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[2] = (0, pad)
    return jnp.pad(x, cfg)


def splitkv_decode_attention(
    q,
    cache,
    mesh,
    *,
    axis: str = "data",
    sm_scale: float | None = None,
    d_v: int | None = None,
    impl: str = "auto",
    num_splits: int | str | None = "auto",
):
    """Sequence-parallel decode attention against a block-sharded QuantKVCache.

    q: [B, 1, h_q, d_k] (model layout; the query transformation happens
    here).  Returns [B, 1, h_q, d_v], replicated along ``axis``.  Composes
    with the in-kernel split: each shard's local kernel may further split its
    block range (``num_splits``), giving mesh x grid sequence parallelism.
    """
    from repro.core.attention import inverse_query_transform, query_transform

    if axis not in mesh.axis_names:
        raise ValueError(
            f"mesh has no axis {axis!r}; available: {tuple(mesh.axis_names)}"
        )
    n = mesh.shape[axis]
    h_kv = cache.kw.shape[1]
    qt = query_transform(q, h_kv)
    nb = cache.kw.shape[2]
    pad = -(-nb // n) * n - nb

    shared = cache.shared_kv
    blk = PS(None, None, axis)  # shard dim 2 (packed blocks) of [B,H,nb,...]
    rep = PS()

    operands = [
        qt,
        _pad_block_axis(cache.kw, pad),
        _pad_block_axis(cache.k_scale, pad),
        _pad_block_axis(cache.k_zero, pad),
    ]
    in_specs = [rep, blk, blk, blk]
    if not shared:
        operands += [
            _pad_block_axis(cache.vw, pad),
            _pad_block_axis(cache.v_scale, pad),
            _pad_block_axis(cache.v_zero, pad),
        ]
        in_specs += [blk, blk, blk]
    operands += [cache.k_res, cache.v_res, cache.pack_blocks, cache.res_len]
    in_specs += [rep] + ([rep] if not shared else []) + [rep, rep]
    if shared:
        operands = [x for x in operands if x is not None]

    def local(*args):
        if shared:
            qt_, kw_, ks_, kz_, kres_, pb_, rl_ = args
            vw_ = vs_ = vz_ = vres_ = None
        else:
            qt_, kw_, ks_, kz_, vw_, vs_, vz_, kres_, vres_, pb_, rl_ = args
        idx = lax.axis_index(axis)
        nb_local = kw_.shape[2]
        lo = idx * nb_local
        pb_local = jnp.clip(pb_ - lo, 0, nb_local)
        rl_local = jnp.where(idx == n - 1, rl_, 0)
        o, lse = bd_ops.bitdecode_attention(
            qt_, kw_, ks_, kz_, vw_, vs_, vz_, kres_, vres_,
            pb_local, rl_local,
            bits=cache.bits, block_n=cache.block_n, sm_scale=sm_scale,
            k_gran=cache.k_gran, shared_kv=shared, d_v=d_v,
            impl=impl, num_splits=num_splits, return_lse=True,
        )
        return merge_collective(o, lse, axis)

    out = jax.shard_map(
        local, mesh=mesh, in_specs=tuple(in_specs), out_specs=rep,
        check_vma=False,
    )(*operands)
    return inverse_query_transform(out)


def splitkv_paged_decode_attention(
    q,
    cache,
    mesh,
    *,
    axis: str = "data",
    sm_scale: float | None = None,
    d_v: int | None = None,
    impl: str = "auto",
    num_splits: int | str | None = "auto",
    page_affine: bool = False,
):
    """Sequence-parallel *paged* decode: shard the page-table **walk**, and
    optionally the pool *storage* behind it.

    The paged cache scatters a sequence's blocks across arbitrary pool pages,
    so the pools themselves have no contiguous block axis to shard; instead
    the ``page_table`` columns (dim 1 of ``[B, nb_max]``) are sharded along
    ``axis`` — each chip walks a contiguous slice of every sequence's table,
    clips ``pack_blocks`` to its slice, and the per-chip flash partials merge
    with the usual lse collectives.  The bf16 residual rides with the last
    shard, exactly as in the dense path.

    ``page_affine=False`` (default) walks the table against *replicated*
    pools — every chip stores every page.  ``page_affine=True`` additionally
    shards the pools' leading (page) axis along the same mesh axis, under
    the page-affine allocator contract (serve/pages.py with ``shards > 1``):
    every page referenced at table column ``j`` lives in shard
    ``j // nb_local`` — the chip that walks that column — so each chip walks
    its table slice against only its own ``n_pages / n`` pages and aggregate
    pool bytes scale with the mesh.  The local walk rebases global page ids
    into the shard (``tbl - idx * pp_local``); entries that violate affinity
    would clamp into range and read garbage, but by the allocator invariant
    the only out-of-shard entries are scratch ids in masked (beyond
    ``pack_blocks``) columns — the same masking the padded-table path
    already relies on.

    q: [B, 1, h_q, d_k]; cache: PagedQuantKVCache.  Returns
    [B, 1, h_q, d_v], replicated along ``axis``.  Composes with the
    in-kernel split (``num_splits``) per chip.  ``shared_kv`` caches (the
    MLA latent pools) shard the same way — one pool set, no V operands —
    with ``d_v`` naming the latent's value slice.
    """
    from repro.core.attention import inverse_query_transform, query_transform

    if axis not in mesh.axis_names:
        raise ValueError(
            f"mesh has no axis {axis!r}; available: {tuple(mesh.axis_names)}"
        )
    n = mesh.shape[axis]
    h_kv = cache.kw.shape[1]
    qt = query_transform(q, h_kv)
    nb = cache.page_table.shape[1]
    pad = -(-nb // n) * n - nb
    table = cache.page_table
    if pad:
        # padded entries point at page 0 (a scratch page): they sit beyond
        # every pack_blocks so the kernel masks them; size nb_max to the
        # axis (serve engine does) to keep the per-step path pad-free
        table = jnp.pad(table, ((0, 0), (0, pad)))

    shared = cache.shared_kv
    rep = PS()
    # pool fields shard their leading (page) axis under page affinity; the
    # residuals stay replicated (they are slot-indexed, not page-indexed)
    pool = PS(axis) if page_affine else rep
    if page_affine and cache.kw.shape[0] % n:
        raise ValueError(
            f"page_affine needs the pool page count ({cache.kw.shape[0]}) "
            f"divisible by the {axis!r} axis size ({n}); allocate the pool "
            "with shards equal to the axis size (serve/pages.py)"
        )
    if shared:
        operands = (
            qt, cache.kw, cache.k_scale, cache.k_zero,
            cache.k_res, table, cache.pack_blocks, cache.res_len,
        )
        in_specs = (rep, pool, pool, pool, rep, PS(None, axis), rep, rep)
    else:
        operands = (
            qt, cache.kw, cache.k_scale, cache.k_zero,
            cache.vw, cache.v_scale, cache.v_zero,
            cache.k_res, cache.v_res, table, cache.pack_blocks, cache.res_len,
        )
        in_specs = (
            (rep,) + (pool,) * 6 + (rep, rep) + (PS(None, axis), rep, rep)
        )

    def local(*args):
        if shared:
            qt_, kw_, ks_, kz_, kres_, tbl_, pb_, rl_ = args
            vw_ = vs_ = vz_ = vres_ = None
        else:
            (qt_, kw_, ks_, kz_, vw_, vs_, vz_, kres_, vres_, tbl_, pb_,
             rl_) = args
        idx = lax.axis_index(axis)
        nb_local = tbl_.shape[1]
        lo = idx * nb_local
        pb_local = jnp.clip(pb_ - lo, 0, nb_local)
        rl_local = jnp.where(idx == n - 1, rl_, 0)
        if page_affine:
            # rebase global page ids into this shard's pool slice; by the
            # allocator's affinity invariant every valid entry in this
            # shard's table columns is shard-local, so only masked entries
            # (scratch ids beyond pb_local) clamp
            pp_local = kw_.shape[0]
            tbl_ = jnp.clip(tbl_ - idx * pp_local, 0, pp_local - 1)
        o, lse = pg_ops.paged_bitdecode_attention(
            qt_, kw_, ks_, kz_, vw_, vs_, vz_, kres_, vres_,
            tbl_, pb_local, rl_local,
            bits=cache.bits, block_n=cache.block_n, sm_scale=sm_scale,
            k_gran=cache.k_gran, shared_kv=shared, d_v=d_v,
            impl=impl, num_splits=num_splits, return_lse=True,
        )
        return merge_collective(o, lse, axis)

    out = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=rep, check_vma=False,
    )(*operands)
    return inverse_query_transform(out)


def splitkv_paged_flush(
    kw_pool, k_scale_pool, k_zero_pool, vw_pool, v_scale_pool, v_zero_pool,
    k_res, v_res, full, dest_page,
    *,
    mesh,
    axis: str = "data",
    page_affine: bool = False,
    **flush_kw,
):
    """The paged residual flush (``residual_flush.ops.paged_residual_flush``,
    same arguments and result) for pools that live on ``mesh``.

    Replicated pools: every chip commits the same pages.  Page-affine pools
    (leading page axis sharded along ``axis``): each chip commits only the
    flushing rows whose destination page it stores.  Its other rows are
    parked, not flushed, on one local page that none of its flushing rows
    writes (at most B of the B + 1 candidates ``[0, B]`` are taken); a
    parked row writes back the page it read, so the page keeps its content.
    """
    rep = PS()
    pool = PS(axis) if page_affine else rep
    pools = [kw_pool, k_scale_pool, k_zero_pool, vw_pool, v_scale_pool,
             v_zero_pool]
    have = [x is not None for x in pools]
    operands = [x for x in pools if x is not None]
    operands += [k_res] + ([v_res] if v_res is not None else [])
    n_pools = sum(have)
    b = full.shape[0]
    if page_affine and kw_pool.shape[0] // mesh.shape[axis] <= b:
        raise ValueError(
            f"page_affine flush needs more than {b} pages per shard, got "
            f"{kw_pool.shape[0] // mesh.shape[axis]}"
        )

    def local(*args):
        pl_ = list(args[:n_pools])
        res = list(args[n_pools:-2])
        fl, dst = args[-2], args[-1]
        if page_affine:
            pp = pl_[0].shape[0]
            idx = lax.axis_index(axis)
            mine = (fl != 0) & (dst // pp == idx)
            dst = dst - idx * pp
            cand = jnp.arange(b + 1, dtype=jnp.int32)
            taken = jnp.any((dst[None, :] == cand[:, None]) & mine[None, :], axis=1)
            park = jnp.argmin(taken).astype(jnp.int32)
            fl = mine.astype(jnp.int32)
            dst = jnp.where(mine, dst, park)
        it = iter(pl_)
        full_pools = [next(it) if h else None for h in have]
        k_r, v_r = res[0], (res[1] if len(res) > 1 else None)
        out = rf_ops.paged_residual_flush(*full_pools, k_r, v_r, fl, dst,
                                          **flush_kw)
        return tuple(x for x in out if x is not None)

    in_specs = (pool,) * n_pools + (rep,) * (len(operands) - n_pools) + (rep, rep)
    out = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=(pool,) * n_pools,
        check_vma=False,
    )(*operands, full, dest_page)
    it = iter(out)
    return tuple(next(it) if h else None for h in have)
