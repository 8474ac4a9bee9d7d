"""Page-pool management for the paged serving engine.

Host-side twin of :class:`repro.core.qcache.PagedQuantKVCache`: the device
holds the pools + page tables, this module decides *which* pool page holds
which request's block.

Commitment accounting (admission control + physical pages in one budget):

* every page the pool has *promised* is counted exactly once, either as a
  **reservation** (``reserved`` — pages a live request may still allocate)
  or as an **allocated page** (``n_used`` — pages on the free list's
  complement, refcounted).  :meth:`PagePool.reserve` admits a request only
  when ``n_used + reserved + n <= capacity``, and :meth:`PagePool.alloc`
  moves one unit from ``reserved`` to ``n_used`` — so every alloc a
  reservation promised is guaranteed to find a free page and steady state is
  preempt-free by construction; a request that cannot reserve stays WAITING
  (admission backpressure).
* **prefix sharing** rides the same budget without double-charging: a shared
  page (refcount > 1 via :meth:`PagePool.retain`) sits in ``n_used`` once,
  no matter how many requests hold it, and a sharer's admission reserves
  only its *private* worst case (``pages_needed - shared_read_blocks`` —
  serve/scheduler.py).  When the original owner retires first, the page
  simply stays in ``n_used`` until its last holder drops it, so the
  commitment total keeps honest count with no reservation hand-off.
  A speculative tail page (the copy-on-write candidate) is *not* discounted:
  its block index can still be flushed, so the sharer keeps one reservation
  unit to cover the COW replica.

**Retention tier** (the third page state, between "committed" and "free"):
a prefix-registered page whose last holder departs does *not* return to the
free list when a ``retainable`` predicate accepts it — it moves to a
RETAINED tier: refcount 0, no holders, off the free list, its prefix-index
chain entry still live.  Retained pages stay counted in ``n_used`` (they
physically occupy pool pages), so the commitment inequality — and with it
the covered-alloc guarantee — is unchanged.  The tier is an LRU:
:meth:`reserve` and :meth:`alloc` reclaim from its oldest end *only when
the free list cannot cover the request*, firing ``on_release`` (prefix
index invalidation) atomically before the page becomes reusable; a prefix
hit on a retained chain promotes the page back to committed via
:meth:`retain` at zero copy cost.  Reclaiming-before-failing means the
engine's preemption loop drains the retained tier before any victim is
preempted — retention can only ever *add* capacity, never steal it.

**Page-affine sharding** (``shards > 1``): the free list splits into
``shards`` contiguous page ranges, matching a pool whose leading (page)
axis is sharded across a mesh axis (``repro.dist.splitkv`` with
``page_affine=True``).  ``alloc(shard=c)`` hands out pages only from range
``c`` — the shard that owns the page-table columns the page will be
referenced from — so every page physically lives on the chip that reads
it and aggregate pool capacity scales with the mesh.  Scratch pages sit in
shard 0 (they are never read as valid data, so their placement is
arbitrary).  Retained-tier reclaim honours the same shard filter.

Physical pages move lazily through the free list — prompt blocks at prefill
adoption, one page per ``block_n`` decoded tokens just before the flush step
that commits it.  ``free`` decrements a refcount and returns the page at
zero (firing ``on_release`` so the scheduler's prefix index can forget it —
unless the page is retainable, in which case the index entry survives).

**Hardening** (every accounting breach raises at the faulting call, naming
the page and its holders, instead of silently corrupting ``committed``):

* each page records its *holders* — the owner tags passed to
  :meth:`PagePool.alloc` / :meth:`PagePool.retain` (the engine passes request
  uids) — and :meth:`PagePool.free` with an owner that is not a holder raises;
* reservations carry an optional per-owner ledger: releasing more units than
  an owner reserved (a double-release) raises naming the owner;
* a covered :meth:`PagePool.alloc` with no reservation outstanding raises
  (it would silently exceed the commitment budget another request was
  promised), and an uncovered alloc refuses to push ``committed`` past
  ``capacity``.

The invariant auditor (`repro.serve.audit`) cross-checks this state against
the page tables, the prefix index, and per-request page lists — including
the retained tier (every retained page must still be registered in the
prefix index, and is exempt from the leak check).

Scratch-page invariant (shared with the paged residual-flush kernel): pool
pages ``[0, n_scratch)`` — one per decode slot — are never allocated.  Page
tables point unassigned entries at the owning slot's scratch page, so a
flush through an idle or not-yet-allocated entry lands in private scratch
and the kernel's per-sequence destinations stay pairwise distinct.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable

import jax.numpy as jnp
import numpy as np

from repro.core import qcache as _qc


class PagePool:
    """Free-list page allocator with commitment accounting, refcounts, an
    LRU retained tier, and optional page-affine sharding."""

    def __init__(self, n_pages: int, *, n_scratch: int, page_bytes: int = 0,
                 metrics=None, shards: int = 1):
        """``page_bytes`` is the per-family byte size of one page across
        every paged layer-cache (the engine measures it from the allocated
        pools), so occupancy can be reported in bytes — a hybrid page covers
        ``n_super`` layer-caches, a dense transformer's covers ``n_layers``,
        and an MLA latent page has no V stream at all.  ``metrics`` (a
        `repro.serve.telemetry.MetricsRegistry`) keeps the pool gauges —
        pages used/reserved/committed/retained and occupancy, with high/low
        water marks — current after every accounting mutation.

        ``shards`` splits the free list into that many contiguous page
        ranges for page-affine allocation (see module docstring); scratch
        pages must fit inside shard 0."""
        if n_pages <= n_scratch:
            raise ValueError(
                f"n_pages={n_pages} must exceed n_scratch={n_scratch}"
            )
        if shards < 1 or n_pages % shards:
            raise ValueError(
                f"n_pages={n_pages} must be a positive multiple of "
                f"shards={shards}"
            )
        if shards > 1 and n_scratch >= n_pages // shards:
            raise ValueError(
                f"n_scratch={n_scratch} must fit inside shard 0 "
                f"({n_pages // shards} pages/shard)"
            )
        self.n_pages = n_pages
        self.n_scratch = n_scratch
        self.page_bytes = page_bytes
        self.shards = shards
        pps = n_pages // shards
        self._pages_per_shard = pps
        self._shard_free: list[deque[int]] = [
            deque(range(max(n_scratch, c * pps), (c + 1) * pps))
            for c in range(shards)
        ]
        if shards == 1:
            # back-compat alias: tests and tooling mutate ``pool._free``
            self._free = self._shard_free[0]
        self._rr = 0  # round-robin shard cursor for unpinned allocs
        self._refcount = np.zeros(n_pages, np.int32)
        self.reserved = 0  # pages promised but not yet allocated
        # RETAINED tier: page -> None, insertion-ordered (oldest first =
        # LRU eviction order).  refcount 0, no holders, not on a free list,
        # still counted in n_used, prefix-index entry still live.
        self._retained: dict[int, None] = {}
        self.reclaim_count = 0  # retained pages reclaimed (registry-free view)
        # page -> owner tags (one per reference, in acquisition order);
        # owner None is the untracked/anonymous caller (unit tests, tooling)
        self._holders: dict[int, list] = {}
        # owner -> reservation units outstanding (only owners that reserve
        # with an explicit tag are tracked; the engine tags request uids)
        self._owner_reserved: dict = {}
        # fired with the page id when a page's last reference drops and it
        # returns to the free list (prefix-index invalidation hook); for a
        # retained page this fires at *reclaim* time instead of free time
        self.on_release: Callable[[int], None] | None = None
        # retention predicate: a page whose last reference drops moves to
        # the RETAINED tier iff this returns True (the scheduler wires it
        # to PrefixIndex.is_registered when retain_prefix is on)
        self.retainable: Callable[[int], bool] | None = None
        self.metrics = metrics
        self._gauges = None
        self._gauge_last: list[float | None] = [None] * 5
        if metrics is not None:
            self._gauges = (
                metrics.gauge("pool_pages_used"),
                metrics.gauge("pool_pages_reserved"),
                metrics.gauge("pool_pages_committed"),
                metrics.gauge("pool_occupancy"),
                metrics.gauge("pool_pages_retained"),
            )
        self._update_gauges()

    def _update_gauges(self) -> None:
        """Refresh the registry gauges after an accounting mutation (the
        gauges' high-water marks record peak commitment between samples):
        writes through cached instrument handles and skips gauges whose
        value did not change."""
        if self.metrics is None:
            return
        vals = (float(self.n_used), float(self.reserved),
                float(self.committed), self.occupancy,
                float(self.n_retained))
        last = self._gauge_last
        for i, (g, v) in enumerate(zip(self._gauges, vals)):
            if last[i] != v:
                g.set(v)
                last[i] = v

    # ------------------------------------------------------------ capacity

    @property
    def capacity(self) -> int:
        """Allocatable pages (scratch excluded)."""
        return self.n_pages - self.n_scratch

    @property
    def n_free(self) -> int:
        return sum(len(d) for d in self._shard_free)

    @property
    def n_used(self) -> int:
        """Allocated pages — includes the retained tier (retained pages
        physically occupy pool pages and are not on any free list)."""
        return self.capacity - self.n_free

    @property
    def n_retained(self) -> int:
        """Pages in the RETAINED tier (refcount 0, index entry live)."""
        return len(self._retained)

    @property
    def committed(self) -> int:
        """Pages spoken for: allocated (shared pages count once, retained
        pages included) + reserved."""
        return self.n_used + self.reserved

    @property
    def occupancy(self) -> float:
        """Physically allocated fraction of the allocatable pool."""
        return self.n_used / max(1, self.capacity)

    @property
    def bytes_in_use(self) -> int:
        """Pool bytes behind allocated pages (per-family ``page_bytes``)."""
        return self.n_used * self.page_bytes

    # -------------------------------------------------------------- shards

    def shard_of(self, page: int) -> int:
        """Shard owning ``page`` — contiguous ranges of
        ``n_pages // shards`` pages, matching a leading-axis device
        sharding of the pools."""
        return page // self._pages_per_shard

    def shard_free(self, shard: int) -> int:
        """Free pages currently in ``shard``."""
        return len(self._shard_free[shard])

    def shard_available(self, shard: int) -> bool:
        """Whether an ``alloc(shard=shard)`` can succeed without
        preemption: a free page in the shard, or a retained page that
        reclaim can convert."""
        if self._shard_free[shard]:
            return True
        return any(self.shard_of(p) == shard for p in self._retained)

    def free_pages(self) -> list[int]:
        """All free pages across shards (audit hook; order is per-shard
        FIFO, shards concatenated)."""
        return [p for d in self._shard_free for p in d]

    # ------------------------------------------------------ retained tier

    def is_retained(self, page: int) -> bool:
        return page in self._retained

    def retained_pages(self) -> list[int]:
        """Retained pages, oldest (next-to-reclaim) first (audit hook)."""
        return list(self._retained)

    def _reclaim_retained(self, n: int, *, shard: int | None = None) -> int:
        """Evict up to ``n`` pages from the LRU-oldest end of the retained
        tier (optionally only pages in ``shard``), returning them to the
        free list.  ``on_release`` fires *before* the page is reusable, so
        the prefix index forgets the chain entry atomically — no window in
        which a lookup can hand out a page that is about to be recycled."""
        done = 0
        for page in list(self._retained):
            if done >= n:
                break
            if shard is not None and self.shard_of(page) != shard:
                continue
            del self._retained[page]
            if self.on_release is not None:
                self.on_release(page)
            self._shard_free[self.shard_of(page)].append(page)
            done += 1
        if done:
            self.reclaim_count += done
            if self.metrics is not None:
                self.metrics.inc("retained_reclaims", done)
            self._update_gauges()
        return done

    def reclaim_retained(self, n: int, *, shard: int | None = None) -> int:
        """Force-reclaim up to ``n`` retained pages (LRU order) — the
        ``evict_storm`` fault site and an operator relief valve.  Returns
        the number actually reclaimed."""
        return self._reclaim_retained(n, shard=shard)

    # -------------------------------------------------------- reservations

    def reserve(self, n: int, *, owner=None) -> bool:
        """Reserve ``n`` future allocations for an admitted request; False
        (and no state change) when the commitment budget cannot guarantee
        them — the scheduler's backpressure signal.  Retained pages are
        reclaimed (LRU-first, index invalidated) exactly as far as needed
        to fit the reservation before backpressure is declared: the
        retained tier never blocks an admission the bare pool could have
        taken.  ``owner`` (the engine passes the request uid) enters the
        per-owner ledger so a later double-``release`` is caught."""
        over = self.committed + n - self.capacity
        if over > 0 and self._retained:
            self._reclaim_retained(over)
        if self.committed + n > self.capacity:
            return False
        self.reserved += n
        if owner is not None:
            self._owner_reserved[owner] = self._owner_reserved.get(owner, 0) + n
        self._update_gauges()
        return True

    def release(self, n: int, *, owner=None) -> None:
        """Return a request's *remaining* (never-allocated) reservation on
        retirement; allocations already converted their unit via
        :meth:`alloc`.  Releasing more than ``owner`` has outstanding (a
        double-release) raises immediately."""
        if n > self.reserved:
            raise ValueError(f"release({n}) exceeds reserved={self.reserved}")
        if owner is not None:
            held = self._owner_reserved.get(owner, 0)
            if n > held:
                raise ValueError(
                    f"double release: owner {owner!r} releases {n} units but "
                    f"has {held} reserved"
                )
            if held - n:
                self._owner_reserved[owner] = held - n
            else:
                self._owner_reserved.pop(owner, None)
        self.reserved -= n
        self._update_gauges()

    def owner_reserved(self, owner) -> int:
        """Outstanding tracked reservation units of ``owner`` (audit hook)."""
        return self._owner_reserved.get(owner, 0)

    # ------------------------------------------------------ physical pages

    def _pop_free(self, shard: int | None) -> int:
        """Pop a free page — from ``shard`` when pinned (page-affine mode),
        else round-robin across shards with free pages.  Reclaims from the
        retained tier only when the relevant free list(s) are dry."""
        if shard is not None:
            if not self._shard_free[shard]:
                self._reclaim_retained(1, shard=shard)
            if not self._shard_free[shard]:
                raise RuntimeError(
                    f"page pool exhausted in shard {shard} "
                    f"(free={self.n_free} elsewhere, "
                    f"retained={self.n_retained})"
                )
            return self._shard_free[shard].popleft()
        if not any(self._shard_free):
            self._reclaim_retained(1)
        for off in range(self.shards):
            c = (self._rr + off) % self.shards
            if self._shard_free[c]:
                self._rr = (c + 1) % self.shards
                return self._shard_free[c].popleft()
        raise RuntimeError("page pool exhausted")

    def alloc(self, *, covered: bool = True, owner=None,
              shard: int | None = None) -> int:
        """Pop a free page (refcount 1, held by ``owner``).

        ``covered=True`` (the serving path) converts one reserved unit into
        an allocated one — guaranteed to succeed *globally* for pages a
        reservation promised (retained pages count as used, so
        ``reserved <= n_free + n_retained`` always holds and a dry free
        list implies a reclaimable retained page); calling it with *no*
        reservation outstanding raises (it would silently spend a unit some
        other request's ``reserve()`` was promised).  ``covered=False``
        (unit tests, tooling) allocates outside any reservation: it leaves
        ``reserved`` untouched and grows ``committed``, reclaiming retained
        pages before refusing to push past ``capacity``.

        ``shard`` (page-affine mode) pins the allocation to one shard's
        page range; a pinned alloc can exhaust that shard even while the
        pool as a whole has pages — the engine's affinity-aware preemption
        loop (`_alloc_page`) guards that case."""
        if covered:
            if not self.reserved:
                raise RuntimeError(
                    "covered alloc() with no reservation outstanding — the "
                    "unit would be stolen from the commitment budget"
                )
            if owner is not None:
                held = self._owner_reserved.get(owner, 0)
                if not held:
                    raise RuntimeError(
                        f"covered alloc() by owner {owner!r} exceeds its "
                        "reservation (0 units left)"
                    )
                if held - 1:
                    self._owner_reserved[owner] = held - 1
                else:
                    self._owner_reserved.pop(owner, None)
        else:
            if self.committed >= self.capacity and self._retained:
                self._reclaim_retained(self.committed - self.capacity + 1)
            if self.committed >= self.capacity:
                raise RuntimeError(
                    f"uncovered alloc() would over-commit the pool "
                    f"(committed={self.committed}, capacity={self.capacity})"
                )
        page = self._pop_free(shard)
        self._refcount[page] = 1
        self._holders[page] = [owner]
        if covered:
            self.reserved -= 1
        self._update_gauges()
        return page

    def retain(self, page: int, *, owner=None) -> bool:
        """Add a reference to an allocated page (prefix sharing), or
        **promote** a RETAINED page back to committed — the prefix-cache
        hit path: the page leaves the LRU, gains refcount 1 and ``owner``
        as its holder, at zero data movement and zero budget change (it
        was already in ``n_used``).  Returns True iff a promotion happened
        (the scheduler counts these as ``prefix_retained_hits``).  Retain
        of a page that is neither allocated nor retained raises."""
        if self._refcount[page] <= 0:
            if page in self._retained:
                del self._retained[page]
                self._refcount[page] = 1
                self._holders[page] = [owner]
                self._update_gauges()
                return True
            raise ValueError(f"retain of unallocated page {page}")
        self._refcount[page] += 1
        self._holders[page].append(owner)
        return False

    def refcount(self, page: int) -> int:
        """Current reference count (0 == free *or* retained). The engine's
        COW trigger: a flush destination with ``refcount > 1`` must be
        replicated first."""
        return int(self._refcount[page])

    def holders(self, page: int) -> list:
        """Owner tags currently holding ``page`` (audit/error reporting)."""
        return list(self._holders.get(page, ()))

    def free(self, page: int, *, owner=None) -> None:
        """Drop one reference.  At refcount zero the page either moves to
        the RETAINED tier (``retainable`` accepts it — its prefix-index
        entry stays live and ``on_release`` does *not* fire) or returns to
        its shard's free list (firing ``on_release``).  Freeing a scratch
        page, a page that is already free or retained, or — with an
        explicit ``owner`` — a page that owner does not hold, raises naming
        the page and its holders."""
        if page < self.n_scratch:
            raise ValueError(
                f"free of scratch page {page} (pages [0, {self.n_scratch}) "
                "are per-slot scratch and are never allocated)"
            )
        if self._refcount[page] <= 0:
            raise ValueError(f"double free of page {page} (refcount 0)")
        held = self._holders[page]
        if owner is not None and owner not in held:
            raise ValueError(
                f"free of page {page} by non-holder {owner!r} "
                f"(held by {held})"
            )
        # anonymous frees drop an anonymous reference first, else the oldest
        held.remove(owner if owner in held else
                    (None if None in held else held[0]))
        self._refcount[page] -= 1
        if self._refcount[page] == 0:
            self._holders.pop(page, None)
            if self.retainable is not None and self.retainable(page):
                # most-recently-departed = most-recently-used: insert at
                # the MRU end of the LRU order
                self._retained[page] = None
                self._update_gauges()
                return
            self._shard_free[self.shard_of(page)].append(page)
            self._update_gauges()
            if self.on_release is not None:
                self.on_release(page)


# --------------------------------------------------------------------------
# Device-side adoption: move bucket-prefill dense caches into the pools
# --------------------------------------------------------------------------

_POOL_FIELDS = _qc._PAGED_POOL_FIELDS


def adopt_prefill(
    paged_caches: list,
    dense_caches: list,
    *,
    slot_ids: list[int],
    lengths: list[int],
    pages_per_req: list[list[int]],
    block_n: int,
    base_blocks: list[int] | None = None,
) -> list:
    """Splice one bucketed prefill into the paged decode state.

    ``paged_caches`` / ``dense_caches``: the per-stack layer-stacked cache
    lists (``state["caches"]``) of the engine's paged state and of the
    just-computed dense prefill (batch = the padded bucket; request ``r``
    occupies row ``r``).  Per request: its first ``lengths[r] // block_n``
    dense packed blocks scatter into pool pages ``pages_per_req[r]``, its
    residual row and occupancy counters copy into decode slot
    ``slot_ids[r]``.  Dense blocks beyond ``pack_blocks`` (right-pad
    pollution) are not copied.

    ``base_blocks`` (prefix sharing) makes the splice *suffix-aware*: the
    dense cache holds only the divergent suffix of each prompt (a
    ``prior=``-mode prefill), whose blocks land *behind* ``base_blocks[r]``
    shared leading blocks already resident in the pools — the slot's
    ``pack_blocks`` becomes ``base_blocks[r] + lengths[r] // block_n`` while
    the copied content and residual stay pure suffix.  The engine points the
    leading page-table columns at the shared (retained) pages separately.

    In page-affine mode the engine allocates ``pages_per_req[r][j]`` from
    the shard owning table column ``base_blocks[r] + j``, so this scatter
    writes each page only on its owning chip.

    Returns the updated paged cache list; page tables are pushed separately
    (:func:`set_page_tables`).
    """
    rows, blks, pages = [], [], []
    for r, pgs in enumerate(pages_per_req):
        for j, pg in enumerate(pgs):
            rows.append(r)
            blks.append(j)
            pages.append(pg)
    base = base_blocks if base_blocks is not None else [0] * len(slot_ids)
    sidx = jnp.asarray(slot_ids, jnp.int32)
    rrow = jnp.arange(len(slot_ids), dtype=jnp.int32)
    pack = jnp.asarray(
        [b + ln // block_n for b, ln in zip(base, lengths)], jnp.int32
    )
    res = jnp.asarray([ln % block_n for ln in lengths], jnp.int32)

    out = []
    for pc, dc in zip(paged_caches, dense_caches):
        upd = {}
        if rows:
            ridx = jnp.asarray(rows, jnp.int32)
            bidx = jnp.asarray(blks, jnp.int32)
            pidx = jnp.asarray(pages, jnp.int32)
            for f in _POOL_FIELDS:
                pool = getattr(pc, f)
                if pool is None:  # shared_kv latent pools have no V side
                    continue
                dn = getattr(dc, f)
                # dn [L, m, H, nb, ...]; advanced idx at dims (1, 3) -> [N, L, H, ...]
                blocks = dn[:, ridx, :, bidx]
                upd[f] = pool.at[:, pidx].set(
                    jnp.moveaxis(blocks, 0, 1).astype(pool.dtype)
                )
        upd["k_res"] = pc.k_res.at[:, sidx].set(
            dc.k_res[:, rrow].astype(pc.k_res.dtype))
        if pc.v_res is not None:
            upd["v_res"] = pc.v_res.at[:, sidx].set(
                dc.v_res[:, rrow].astype(pc.v_res.dtype))
        upd["pack_blocks"] = pc.pack_blocks.at[:, sidx].set(pack)
        upd["res_len"] = pc.res_len.at[:, sidx].set(res)
        out.append(dataclasses.replace(pc, **upd))
    return out


def cow_pages(paged_caches: list, src: list[int], dst: list[int]) -> list:
    """Copy-on-write replication across every stacked paged cache: pool pages
    ``dst[i]`` become bitwise replicas of ``src[i]`` (all six pool fields,
    all layers — ``qcache.copy_pages``).  The engine calls this just before
    a decode flush whose destination page has refcount > 1, after repointing
    the flushing request's page-table column at ``dst``.  In page-affine
    mode ``src[i]`` and ``dst[i]`` are in the same shard by construction
    (both back the same table column), so the copy is shard-local."""
    return [_qc.copy_pages(pc, src, dst) for pc in paged_caches]


def set_page_tables(paged_caches: list, table: np.ndarray) -> list:
    """Push the host page table ([B, nb_max] int32) into every stacked paged
    cache (broadcast along the layer dims — all layers share one table)."""
    t = jnp.asarray(table, jnp.int32)
    return [
        dataclasses.replace(
            pc, page_table=jnp.broadcast_to(t, pc.page_table.shape)
        )
        for pc in paged_caches
    ]
