"""Paged continuous-batching serving engine — single decode path, driven by
the model's declared cache family (``model.paged_spec()``).

The engine composes the serving-layer pieces into one per-cycle loop:

* :class:`~repro.serve.scheduler.Scheduler` — request lifecycle
  (WAITING → PREFILL → DECODE → DONE), strict-FIFO admission gated on slot
  *and* page availability, length-bucketed prefill grouping;
* :class:`~repro.serve.pages.PagePool` — free-list page allocator with
  admission reservations (preempt-free steady state) and refcounts;
* the paged decode state (``model.init_paged_decode_state``): per-layer
  page pools + per-slot page tables, decoded through
  ``kernels/paged_bitdecode`` with the fused paged residual flush on the
  append path (``qcache.paged_append_decode``).

**Every cache family decodes through the page table.**  What differs per
family is declared, not forked (`repro.models.family.PagedSpec`):

* plain/GQA attention — split K/V pools, pow2-bucketed ragged prefill,
  prefix sharing + speculative-tail COW;
* MLA — a single ``shared_kv`` latent pool set per stack (V is a channel
  slice of the dequantized latent, in-kernel), same prefix sharing: the
  suffix prefill expands dequantized latent prior pages through each
  layer's up-projections;
* hybrid (Mamba2 + shared attention) — the attention caches page; the
  constant-size SSM recurrent states are ``side_state`` the engine splices
  per slot at admission and that never touch the page table.  Recurrent
  state cannot absorb right-padding, so admission groups are *exact-length*
  (``exact_prefill``) and prefix sharing stays off (``supports_prior``);
* no-KV recurrent models (xLSTM) — ``PagedSpec(paged=False)``: served by a
  thin exact-length shim (per-request prefill spliced into the batched
  dense state) that shares this engine's scheduler and decode cycle;
  ``paged_spec() is None`` (enc-dec, VLM stub) means the engine cannot feed
  the model's prefill at all and refuses at construction.

One cycle (:meth:`ServeEngine.step`):

1. admit waiting requests into free slots; paged families run **one jitted
   prefill per suffix-length bucket** (the scheduler's prefix index maps
   shared leading blocks onto resident pool pages, and suffix tokens attend
   the dequantized shared prefix via ``model.prefill(prior=...)``), adopt
   the resulting blocks into freshly allocated pages behind the shared ones
   (``adopt_prefill(base_blocks=...)``), and splice any declared dense
   side-state; the shim prefills per request at exact length;
2. (paged) allocate the destination page for any sequence whose residual
   fills on this step; a destination holding a refcount>1 page (speculative
   shared tail) is **copy-on-written** first (``qcache.copy_pages``);
3. push the page table if it changed, then run one jitted batched decode
   step over all slots — through the cross-chip split-KV path when a mesh
   is attached and the cycle is long-context/low-occupancy;
4. advance per-token accounting (one shared code path: ``req.pos``
   increments every decoded token, budget-capped retirement counts
   ``budget_retired`` exactly once), retire finished requests, record
   latency/occupancy.

Idle slots keep decoding garbage into their private scratch pages (their
page-table rows point at scratch, see serve/pages.py) — wasted lanes, never
corruption.

**Pressure handling** (docs/SERVING.md §10).  Under
``reserve_policy="expected"`` the scheduler under-reserves and a request
that outlives its expected decode length extends its reservation one page
at a time in ``_alloc_page``; when the pool cannot grant the unit the
engine **preempts** a victim (``preempt_policy``: ``"youngest"`` /
``"fewest_pages"``) — its pages are freed through the refcounted pool (so
shared prefixes survive via their other holders) and it requeues at the
FIFO head.  Re-admission re-prefills its prompt through the ordinary
suffix path, then **replays** its already-decoded tokens teacher-forced
through the decode path — the same computation that built them, so the
quantized cache (and every future token) is reconstructed bitwise; the
parked decoded-but-unfed token is restored after the replay, continuing
the *exact* token stream of a never-preempted run.

**Lifecycle guards**: per-request ``deadline_s`` TTLs retire to EXPIRED at
the top of each cycle, :meth:`ServeEngine.cancel` retires to CANCELLED, and
a poisoned step (non-finite logits row / out-of-vocab token) retires just
that request ERRORED — the engine loop and every other slot continue.

**Self-checking**: ``audit_every=N`` cross-checks pool refcounts vs page
tables vs prefix index vs per-request page lists every N cycles
(`repro.serve.audit`); ``faults=FaultPlan(...)`` injects deterministic
failures at the named sites (`repro.serve.faults`) for chaos tests.

**Telemetry** (docs/OBSERVABILITY.md, `repro.serve.telemetry`): every
lifecycle counter lives in a shared :class:`MetricsRegistry` (the ``stats``
property keeps the historical dict view), each cycle is decomposed into
timed phases — ``schedule``, ``prefill``, ``decode_dispatch``,
``device_wait`` (an explicit ``jax.block_until_ready`` boundary), and
``advance`` — feeding per-phase histograms plus the derived
``host_stall_fraction`` / ``device_idle_gap_s`` metrics, and token
latencies split into TTFT (submission → first token, queue wait included)
and TPOT (inter-token) series.  ``trace=True`` additionally records a
structured event log (request lifecycle spans, COW / preemption /
speculative / audit / fault instants, per-phase complete events) that
exports as JSONL or Chrome ``trace_event`` JSON for Perfetto.  All of it is
host-side observation only — enabling telemetry never changes a computed
token (the bitwise-parity suites run with tracing on).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import attention as catt
from repro.core import qcache
from repro.kernels.bitdecode import ops as bd_ops
from repro.models.family import get_path, set_path
from repro.serve import pages as pg
from repro.serve.audit import audit_engine
from repro.serve.scheduler import (  # noqa: F401 (Phase/Request re-exported)
    Phase,
    Request,
    Scheduler,
    bucket_for,
)
from repro.serve.telemetry import MetricsRegistry, Tracer

#: cycle phases in execution order -> the registry histogram each feeds
#: (explicit literals so docs/OBSERVABILITY.md's metric catalog can be
#: drift-checked against the source — scripts/check_docs.py)
PHASE_METRICS = {
    "schedule": "phase_schedule_s",
    "prefill": "phase_prefill_s",
    "decode_dispatch": "phase_decode_dispatch_s",
    "device_wait": "phase_device_wait_s",
    "advance": "phase_advance_s",
}

#: timing-derived ``summary()`` keys — everything a determinism comparison
#: must strip before asserting two runs equal (tests/test_serve_pressure.py)
TIMING_SUMMARY_KEYS = frozenset({
    "wall_s", "tokens_per_s", "latency_p50_ms", "latency_p99_ms",
    "ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms", "tpot_p99_ms",
    "queue_wait_p50_ms", "queue_wait_p99_ms", "e2e_p50_ms", "e2e_p99_ms",
    "host_stall_fraction", "phase_s",
})

#: the engine's lifecycle counters (one registry entry each; the ``stats``
#: property and ``summary()`` expose exactly these, preserving the
#: pre-registry dict interface)
STAT_COUNTERS = (
    "decoded_tokens", "steps", "prefill_calls", "splitkv_steps",
    "prefill_tokens", "prefill_tokens_saved", "cow_copies",
    # retirement breakdown (each request counts in at most one):
    # budget_retired = hit max_new_tokens without EOS
    "budget_retired", "preempted", "preempt_remat_tokens",
    "expired", "cancelled", "errored", "audits", "faults_injected",
    # prefix-retention tier (docs/SERVING.md §14): retained pages evicted
    # back to the free list (LRU reclaim under pressure or evict_storm)
    "retained_reclaims",
    # self-speculative decoding (docs/SERVING.md §11)
    "spec_cycles", "spec_draft_tokens",
    "spec_accepted_tokens", "spec_rejected_tokens",
    # async overlapped runtime (docs/SERVING.md §13):
    # completions_enqueued = terminal retirements handed to the background
    # completion thread; discarded_steps = in-flight decode results consumed
    # after their request already left the slot (retirement/preemption lag)
    "completions_enqueued", "discarded_steps",
)


def _percentile(xs: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


class _PhaseTimer:
    """Accumulating timer for one named cycle phase: elapsed wall time adds
    into the engine's per-cycle accumulator (several with-blocks of the same
    phase within a cycle sum), and with tracing on, each block additionally
    emits one Chrome complete event on the engine track."""

    __slots__ = ("engine", "name", "t0")

    def __init__(self, engine, name: str):
        self.engine = engine
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        acc = self.engine._phase_acc
        acc[self.name] = acc.get(self.name, 0.0) + dt
        tracer = self.engine.tracer
        if tracer is not None:
            tracer.complete(self.name, t0=self.t0, dur_s=dt, cat="engine")
        return False


class ServeEngine:
    def __init__(self, model, params, *, slots: int = 8, max_seq: int = 2048,
                 eos_id: int | None = None, impl: str = "auto",
                 quant_impl: str = "auto", paged: bool | None = None,
                 n_pages: int | None = None, min_bucket: int = 16,
                 mesh=None, splitkv_axis: str = "data",
                 splitkv: str = "auto", share_prefix: bool = True,
                 spec_tail: bool = True, retain_prefix: bool = False,
                 page_affine: bool = False,
                 reserve_policy: str = "worst_case",
                 expected_quantile: float = 0.5,
                 preempt_policy: str = "youngest", audit_every: int = 0,
                 faults=None, strict: bool = False,
                 guard_logits: bool = True, clock=None,
                 spec_k: int = 1, spec_bits: int | None = None,
                 trace: bool | Tracer = False,
                 metrics: MetricsRegistry | None = None,
                 metrics_every: int = 0, metrics_sink=None,
                 async_runtime: bool = False, async_window: int = 2,
                 completion_queue: int = 64, watchdog_s: float = 30.0,
                 detokenizer=None, on_complete=None):
        """``paged=None`` follows the model's ``paged_spec()`` (paged when it
        declares a paged family); ``paged=False`` forces the exact-length
        shim for any token-prefill model (debug/baseline path); ``paged=True``
        raises if the model declares no paged family.  ``n_pages`` bounds the
        KV pool (default: full provisioning, ``slots * nb_max`` + scratch —
        lower it to oversubscribe and exercise admission backpressure).
        ``mesh``/``splitkv_axis`` attach the cross-chip split-KV decode path;
        ``splitkv`` is the routing policy: 'auto' (engage on long-context
        low-occupancy cycles), 'always', 'never'.  ``share_prefix`` enables
        the scheduler's prompt-prefix index for families that support suffix
        prefill (``PagedSpec.supports_prior``); ``spec_tail`` additionally
        adopts a matching donor block as the speculative flush destination
        when a prompt ends mid-block — the copy-on-write candidate (see
        docs/SERVING.md).

        Prefix retention + page affinity (docs/SERVING.md §14):
        ``retain_prefix=True`` keeps prefix-registered pages in the pool's
        evictable RETAINED tier after their last holder departs, so a later
        admission over the same prompt re-adopts them at zero prefill cost;
        reclaim (LRU) happens only when the free list runs dry, *before*
        any preemption fires.  ``page_affine=True`` (requires ``mesh`` and
        a paged family) shards the page pool's free list per mesh-axis
        shard and pins every page to the shard owning its page-table
        column, matching a leading-axis device sharding of the pools
        (`repro.dist.state_specs.decode_state_specs` with
        ``page_affine=True``) — aggregate pool capacity then scales with
        the mesh instead of being replicated per chip.

        Pressure handling (docs/SERVING.md §10): ``reserve_policy`` /
        ``expected_quantile`` select the admission reservation (worst-case
        lifetime vs expected decode length — serve/scheduler.py);
        ``preempt_policy`` picks the victim when a reservation extension
        cannot be granted: ``"youngest"`` (latest admission) or
        ``"fewest_pages"`` (cheapest rematerialization).  ``audit_every=N``
        runs the invariant auditor every N cycles (0 disables);
        ``faults`` attaches a `repro.serve.faults.FaultPlan`;
        ``strict=True`` makes never-admittable submissions raise instead of
        retiring REJECTED; ``guard_logits=False`` disables the per-row
        poisoned-step isolation (benchmarking); ``clock`` (default
        ``time.monotonic``) drives ``deadline_s`` TTL enforcement.

        Self-speculative decoding (docs/SERVING.md §11): ``spec_k > 1``
        decodes up to ``spec_k`` tokens per cycle — a draft pass against the
        truncated ``spec_bits``-bit read of the *same* pools proposes
        ``spec_k - 1`` continuations, one batched full-fidelity verify scan
        accepts the longest exactly-matching prefix (greedy engine, so
        acceptance is exact token equality and the output stream is bitwise
        identical to ``spec_k = 1``).  ``spec_bits`` defaults to
        ``min(2, kv_bits)``.  Speculative cycles never route through the
        cross-chip split-KV step (the per-cycle heuristic stays off).

        Telemetry (docs/OBSERVABILITY.md): ``trace=True`` (or an existing
        `repro.serve.telemetry.Tracer`) records the structured event log —
        request lifecycle spans, COW/preempt/spec/audit/fault instants,
        per-phase complete events — exportable as JSONL or Chrome trace
        JSON; tracing off costs nothing (every call site is guarded).
        ``metrics`` shares an external
        `repro.serve.telemetry.MetricsRegistry` (default: a private one);
        ``metrics_every=N`` emits a snapshot every N cycles to
        ``metrics_sink`` (a callable receiving the snapshot dict; default
        prints the Prometheus text exposition).

        Async overlapped runtime (docs/SERVING.md §13):
        ``async_runtime=True`` replaces the stop-the-world cycle with the
        overlapped runtime (`repro.serve.async_runtime.AsyncRunner`) —
        decode steps dispatch without a per-cycle ``block_until_ready``
        (next-token argmax stays on device), the host syncs only at
        token-consumption boundaries lagging the dispatch frontier by at
        most ``async_window`` steps, prefill admission overlaps in-flight
        decode, and terminal requests flow to a background
        detokenize/completion thread through a bounded queue of
        ``completion_queue`` entries (a blocking put/drain that exceeds
        ``watchdog_s`` raises `repro.serve.async_runtime.DeadlockError`
        instead of wedging).  ``detokenizer`` (tokens -> text) and
        ``on_complete`` (called with each CompletionRecord) run on that
        thread.  Output token streams are bitwise identical to
        ``async_runtime=False`` — the sync cycle stays available as the
        oracle (tests/test_serve_async.py).  With ``spec_k > 1`` the
        speculative cycle itself runs unoverlapped (it already amortizes
        host syncs — two per up-to-``spec_k`` tokens) but completions
        still route through the background thread."""
        self.model = model
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        if mesh is not None:
            # the host updates the sharded state with eager ops (prefill
            # adoption, COW, table pushes); only Auto-typed mesh axes allow
            # that outside a mesh context
            mesh = jax.sharding.Mesh(
                mesh.devices, mesh.axis_names,
                axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names),
            )
        self.mesh = mesh
        self.splitkv_axis = splitkv_axis
        self.splitkv = splitkv
        if preempt_policy not in ("youngest", "fewest_pages"):
            raise ValueError(f"unknown preempt_policy {preempt_policy!r}")
        self.preempt_policy = preempt_policy
        self.audit_every = audit_every
        self.faults = faults
        self.guard_logits = guard_logits
        self.clock = clock if clock is not None else time.monotonic
        self._cycle = 0

        # --- telemetry (docs/OBSERVABILITY.md) ---------------------------
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = (
            trace if isinstance(trace, Tracer)
            else (Tracer() if trace else None)
        )
        self.metrics_every = int(metrics_every)
        self.metrics_sink = metrics_sink
        for name in STAT_COUNTERS:
            self.metrics.counter(name)
        for hist in PHASE_METRICS.values():
            self.metrics.histogram(hist)
        self.metrics.histogram("cycle_s")
        self.metrics.histogram("device_idle_gap_s")
        # async runtime: wall time the dispatch pipeline sat empty while
        # work remained (the overlap-aware host-stall numerator, §13)
        self.metrics.histogram("device_starved_s")
        self.metrics.histogram("ttft_s")
        self.metrics.histogram("tpot_s")
        self.metrics.histogram("queue_wait_s")
        self.metrics.histogram("e2e_latency_s")
        self._phase_acc: dict[str, float] = {}
        self._cycle_worked = False
        # explicit first-work -> last-work window: the honest wall_s
        # fallback for callers driving step() themselves
        self._work_t0: float | None = None
        self._work_t1: float | None = None
        self._ttft_s: list[float] = []
        self._tpot_s: list[float] = []
        self._queue_wait_s: list[float] = []
        self._e2e_s: list[float] = []
        if faults is not None and getattr(faults, "on_fire", None) is None:
            faults.on_fire = self._on_fault
        # delayed-release fault parking lot: (ready_cycle, uid, pages)
        self._deferred: list[tuple[int, int, list[int]]] = []
        cfg = getattr(model, "cfg", None)

        spec = model.paged_spec() if hasattr(model, "paged_spec") else None
        if spec is None:
            raise ValueError(
                "model declares no serveable cache family (paged_spec() is "
                "None): its prefill needs inputs beyond tokens"
            )
        if paged and not spec.paged:
            raise ValueError(
                "model declares no paged decode capability "
                "(see repro.models.family.PagedSpec)"
            )
        self.spec = spec
        self.paged = (spec is not None and spec.paged) if paged is None else bool(paged)
        self.block_n = spec.block_n if spec is not None else getattr(cfg, "kv_block", 128)
        self._h_kv = spec.n_kv_heads if spec is not None else getattr(cfg, "n_kv_heads", 1)

        # self-speculative decoding (draft against the truncated-bit read of
        # the same pools; one batched verify scan; docs/SERVING.md §11)
        self.spec_k = int(spec_k)
        if self.spec_k < 1:
            raise ValueError(f"spec_k={spec_k} must be >= 1")
        kv_bits = getattr(cfg, "kv_bits", 4)
        self.spec_bits = int(spec_bits) if spec_bits is not None else min(2, kv_bits)
        if not 1 <= self.spec_bits <= kv_bits:
            raise ValueError(
                f"spec_bits={self.spec_bits} outside [1, kv_bits={kv_bits}]"
            )
        self._draft = self._verify = None
        if self.spec_k > 1:
            from repro.serve import speculative as _spec_mod

            self._draft = _spec_mod.make_draft_fn(
                model, spec_k=self.spec_k, spec_bits=self.spec_bits,
                quant_impl=quant_impl,
            )
            self._verify = _spec_mod.make_verify_fn(
                model, spec, impl=impl, quant_impl=quant_impl
            )

        self._impl = impl
        self._quant_impl = quant_impl
        # one jitted decode step (static shapes) shared by every family, and
        # the host-side next-token buffer (one device->host pull per cycle)
        self._step = jax.jit(
            lambda p, s, t: model.decode_step(
                p, s, t, impl=impl, quant_impl=quant_impl
            )
        )
        self._step_splitkv = None
        if mesh is not None and splitkv_axis not in getattr(mesh, "axis_names", ()):
            raise ValueError(
                f"mesh has no axis {splitkv_axis!r}; available: "
                f"{tuple(getattr(mesh, 'axis_names', ()))}"
            )
        if mesh is not None and splitkv != "never":
            _affine = bool(page_affine)

            def _split_step(p, s, t):
                with catt.use_splitkv(mesh, splitkv_axis,
                                      page_affine=_affine):
                    return model.decode_step(
                        p, s, t, impl=impl, quant_impl=quant_impl
                    )
            self._step_splitkv = jax.jit(_split_step)

        self.tokens = np.zeros((slots, 1), np.int32)
        self._occupancy: list[float] = []

        self.page_affine = bool(page_affine)
        if self.page_affine and mesh is None:
            raise ValueError("page_affine=True requires a mesh")
        if self.page_affine and not self.paged:
            raise ValueError("page_affine=True requires a paged family")
        if self.page_affine and splitkv == "never":
            raise ValueError(
                "page_affine=True needs the sharded split-KV walk "
                "(splitkv='auto' or 'always')"
            )
        if self.paged:
            nb_max = -(-max_seq // self.block_n)
            if mesh is not None:
                n = int(mesh.shape[splitkv_axis])  # pad-free sharded table walk
                nb_max = -(-nb_max // n) * n
            self.nb_max = nb_max
            shards = int(mesh.shape[splitkv_axis]) if self.page_affine else 1
            self._pool_shards = shards
            self._nb_local = nb_max // shards
            if n_pages is not None:
                self.n_pages = n_pages
            else:
                # full provisioning; page-affine adds one slot-page per
                # shard so shard 0's scratch range doesn't eat into its
                # allocatable share (n_pages stays a multiple of shards)
                self.n_pages = slots * nb_max + slots * shards
            self.state = model.init_paged_decode_state(
                slots, n_pages=self.n_pages, nb_max=nb_max
            )
            # the allocated pools must match the declared family — catches a
            # model whose spec and init_paged_decode_state drift apart
            first = self.state["caches"][0]
            if (first.shared_kv != spec.shared_kv
                    or first.kw.shape[-1] != spec.d_k
                    or (not spec.shared_kv
                        and first.vw.shape[-1] != spec.d_v)):
                raise ValueError(
                    "paged_spec() disagrees with init_paged_decode_state: "
                    f"declared (shared_kv={spec.shared_kv}, d_k={spec.d_k}, "
                    f"d_v={spec.d_v}) vs allocated (shared_kv="
                    f"{first.shared_kv}, d_k={first.kw.shape[-1]})"
                )
            # per-family page size in bytes: one table column spans every
            # paged layer-cache (spec.page_layers of them), measured exactly
            # from the allocated pools
            self.kv_page_bytes = sum(
                getattr(pc, f).nbytes
                for pc in self.state["caches"]
                for f in qcache._PAGED_POOL_FIELDS
                if getattr(pc, f) is not None
            ) // self.n_pages
            if self.page_affine:
                # place the pools page-sharded at rest: each chip holds
                # n_pages/shards pages (plus its table-column slice), so
                # per-chip pool bytes stay constant as the mesh grows
                from jax.sharding import NamedSharding
                from repro.dist.state_specs import decode_state_specs
                if self.n_pages % shards:
                    raise ValueError(
                        f"page_affine needs n_pages ({self.n_pages}) "
                        f"divisible by the {splitkv_axis!r} axis size "
                        f"({shards})"
                    )
                specs = decode_state_specs(
                    model, mesh, global_batch=slots, seq_ax=splitkv_axis,
                    paged=True, n_pages=self.n_pages, nb_max=nb_max,
                    page_affine=True,
                )
                self.state = jax.device_put(
                    self.state,
                    jax.tree.map(
                        lambda s: None if s is None else NamedSharding(mesh, s),
                        specs, is_leaf=lambda x: x is None,
                    ),
                )
            self.pool = pg.PagePool(
                self.n_pages, n_scratch=slots, page_bytes=self.kv_page_bytes,
                metrics=self.metrics, shards=self._pool_shards,
            )
            share = share_prefix and spec.supports_prior
            self.retain_prefix = retain_prefix and share
            self.sched = Scheduler(
                slots=slots, pool=self.pool, block_n=self.block_n,
                max_seq=max_seq, min_bucket=min_bucket,
                share_prefix=share, spec_tail=spec_tail and share,
                retain_prefix=self.retain_prefix,
                exact_buckets=spec.exact_prefill,
                reserve_policy=reserve_policy,
                expected_quantile=expected_quantile,
                strict=strict, clock=self.clock, metrics=self.metrics,
                namespace=(
                    f"{getattr(cfg, 'name', 'model')}/b{getattr(cfg, 'kv_bits', 4)}"
                    f"/n{self.block_n}/{getattr(cfg, 'kv_gran', 'channel')}"
                ),
            )
            # host mirror of the device page table; unassigned entries point
            # at the slot's scratch page (flush-destination injectivity)
            self._table = np.broadcast_to(
                np.arange(slots, dtype=np.int32)[:, None], (slots, nb_max)
            ).copy()
            self._table_dirty = False

            def on_mesh(fn):
                # the compiler never partitions a Mosaic kernel: on a mesh
                # the prefill runs whole on every chip, under shard_map
                if mesh is None:
                    return fn
                return jax.shard_map(
                    fn, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
                    out_specs=jax.sharding.PartitionSpec(), check_vma=False,
                )

            # one jitted bucketed prefill; jit cache keys on the padded
            # token shape = (slots, bucket_len) -> one compile per bucket
            # (per exact length for exact_prefill families)
            if spec.exact_prefill:
                self._prefill = jax.jit(on_mesh(
                    lambda p, toks: model.prefill(p, {"tokens": toks},
                                                  toks.shape[1])
                ))
            else:
                self._prefill = jax.jit(on_mesh(
                    lambda p, toks, lengths: model.prefill(
                        p, {"tokens": toks}, toks.shape[1], lengths=lengths,
                        quant_impl=quant_impl,
                    )
                ))
            # shared-prefix suffix prefill: dequantizes the prior pages from
            # the pools and attends them from the divergent suffix; the jit
            # cache keys on (bucket_len, padded prior blocks) — prior width
            # is bucketed to powers of two to bound compile count
            @on_mesh
            def _prefill_over_prior(p, toks, lengths, prior, prior_len):
                return model.prefill(
                    p, {"tokens": toks}, toks.shape[1],
                    lengths=lengths, prior=prior, prior_len=prior_len,
                    quant_impl=quant_impl,
                )

            def _suffix_prefill(p, caches, toks, lengths, pages, prior_len):
                prior = [qcache.dequant_prior(c, pages) for c in caches]
                return _prefill_over_prior(p, toks, lengths, prior, prior_len)

            self._prefill_shared = jax.jit(_suffix_prefill)
        else:
            # exact-length shim: dense state, per-request prefill, no pool
            self.pool = None
            self.retain_prefix = False
            self._pool_shards = 1
            self.sched = Scheduler(
                slots=slots, pool=None, block_n=self.block_n, max_seq=max_seq,
                share_prefix=False, spec_tail=False, exact_buckets=True,
                strict=strict, clock=self.clock, metrics=self.metrics,
            )
            self.state = model.init_decode_state(slots, max_seq)
            self._prefill = jax.jit(
                lambda p, b: model.prefill(p, b, self.max_seq)
            )

        # --- async overlapped runtime (docs/SERVING.md §13) ---------------
        self.async_runtime = bool(async_runtime)
        self._runner = None
        self._completions = None
        if self.async_runtime:
            from repro.serve.async_runtime import AsyncRunner, CompletionWorker

            self._completions = CompletionWorker(
                queue_size=completion_queue, watchdog_s=watchdog_s,
                detokenizer=detokenizer, on_complete=on_complete,
            )
            if self.spec_k == 1:
                self._runner = AsyncRunner(
                    self, window=async_window, watchdog_s=watchdog_s
                )

    # ------------------------------------------------------------ public

    @property
    def stats(self) -> dict:
        """Lifecycle counters as a plain dict (the pre-telemetry ``stats``
        interface, now a read-only view of the metrics registry)."""
        return {k: int(self.metrics.value(k)) for k in STAT_COUNTERS}

    def _phase(self, name: str) -> _PhaseTimer:
        """Timer for one cycle phase (``with self._phase("schedule"): ...``)."""
        return _PhaseTimer(self, name)

    def _on_fault(self, site: str, cycle: int, uid) -> None:
        """``FaultPlan.on_fire`` hook: count and trace every injected fault."""
        self.metrics.inc("faults_injected")
        if self.tracer is not None:
            self.tracer.instant(
                "fault", args={"site": site, "cycle": cycle, "uid": uid}
            )

    def submit(self, req: Request) -> bool:
        """Queue ``req``; False when it was retired REJECTED at submission
        (``req.error`` names the reason; raises instead under ``strict``)."""
        ok = self.sched.submit(req)
        if self.tracer is not None:
            if ok:
                self.tracer.begin("queue", uid=req.uid, cat="request")
            else:
                self.tracer.instant("rejected", uid=req.uid, cat="request")
        return ok

    def cancel(self, uid: int) -> Request | None:
        """Cancel a waiting or active request by uid; returns the retired
        request (phase CANCELLED, resources released, page-table row reset)
        or None when no live request has that uid."""
        for req in list(self.sched.waiting):
            if req.uid == uid:
                self.sched.waiting.remove(req)
                self._retire(req, Phase.CANCELLED, reason="cancelled")
                return req
        for req in list(self.sched.active.values()):
            if req.uid == uid:
                self._retire(req, Phase.CANCELLED, reason="cancelled")
                return req
        return None

    def audit(self):
        """Run the invariant auditor now (`repro.serve.audit.audit_engine`)."""
        self.metrics.inc("audits")
        report = audit_engine(self)
        if self.tracer is not None:
            self.tracer.instant(
                "audit", args={"violations": len(report.violations)}
            )
        return report

    def run(self, max_cycles: int = 10_000):
        t0 = time.perf_counter()
        cycles = 0
        while self._has_work() and cycles < max_cycles:
            self.step()
            cycles += 1
            if self._runner is not None:
                self._runner.check_liveness()
        if self._completions is not None:
            # every enqueued completion processed before the drain audit
            self._completions.drain()
        if self.paged and self.audit_every:
            self.audit().raise_if_violations()  # clean at drain
        return self.summary(wall_s=time.perf_counter() - t0)

    def close(self) -> None:
        """Stop the background completion thread (async runtime); idempotent
        and a no-op for the synchronous engine."""
        if self._completions is not None:
            self._completions.close()

    def summary(self, *, wall_s: float | None = None) -> dict:
        """Engine statistics; callers driving :meth:`step` themselves (the
        offered-load bench) pass their own wall-clock window.  Every
        timing-derived key is listed in `TIMING_SUMMARY_KEYS` so determinism
        comparisons know exactly what to strip."""
        if wall_s is None:
            # explicit first-work -> last-work window (never fabricated from
            # latency sums): an engine that did no decode work reports 0
            if self._work_t0 is not None and self._work_t1 is not None:
                wall_s = self._work_t1 - self._work_t0
            else:
                wall_s = 0.0
        stats = self.stats
        cycle_total = self.metrics.histogram("cycle_s").total
        wait_total = self.metrics.histogram("phase_device_wait_s").total
        # legacy latency_* keys alias TPOT (steady-state inter-token
        # latency); they fall back to TTFT when every request emitted a
        # single token and no inter-token gap was ever observed
        lat = self._tpot_s if self._tpot_s else self._ttft_s
        out = {
            **stats,
            "wall_s": wall_s,
            "tokens_per_s": (
                stats["decoded_tokens"] / wall_s if wall_s > 0 else 0.0
            ),
            **{f"sched_{k}": v for k, v in self.sched.stats.items()},
            "latency_p50_ms": 1e3 * _percentile(lat, 50),
            "latency_p99_ms": 1e3 * _percentile(lat, 99),
            "ttft_p50_ms": 1e3 * _percentile(self._ttft_s, 50),
            "ttft_p99_ms": 1e3 * _percentile(self._ttft_s, 99),
            "tpot_p50_ms": 1e3 * _percentile(self._tpot_s, 50),
            "tpot_p99_ms": 1e3 * _percentile(self._tpot_s, 99),
            "queue_wait_p50_ms": 1e3 * _percentile(self._queue_wait_s, 50),
            "queue_wait_p99_ms": 1e3 * _percentile(self._queue_wait_s, 99),
            "e2e_p50_ms": 1e3 * _percentile(self._e2e_s, 50),
            "e2e_p99_ms": 1e3 * _percentile(self._e2e_s, 99),
            # fraction of cycle time the host was NOT waiting on the device
            # — the async-runtime ROADMAP item exists to shrink this.  The
            # overlapped runtime measures it directly as dispatch-pipeline
            # starvation (below); the sync cycle infers it from device_wait
            # (host working == device idle holds only without overlap)
            "host_stall_fraction": (
                1.0 - min(1.0, wait_total / cycle_total)
                if cycle_total > 0 else 0.0
            ),
            "phase_s": {
                **{
                    name: self.metrics.histogram(h).total
                    for name, h in PHASE_METRICS.items()
                },
                "cycle": cycle_total,
            },
        }
        if self._runner is not None and self._runner.dispatched > 0:
            # overlap-aware attribution: time the dispatch pipeline sat
            # empty (in-flight window drained while work remained), not
            # time-not-in-device_wait — under overlap the host working no
            # longer implies the device is idle (docs/OBSERVABILITY.md)
            starved = self.metrics.histogram("device_starved_s").total
            out["host_stall_fraction"] = (
                min(1.0, starved / cycle_total) if cycle_total > 0 else 0.0
            )
        if self.spec_k > 1:
            out["spec_accept_rate"] = (
                stats["spec_accepted_tokens"]
                / max(1, stats["spec_draft_tokens"])
            )
        if self.paged:
            out.update(
                occupancy_mean=float(np.mean(self._occupancy)) if self._occupancy else 0.0,
                occupancy_max=float(np.max(self._occupancy)) if self._occupancy else 0.0,
                # per-family page accounting (repro.models.family.PagedSpec):
                # one table column spans spec.page_layers layer-caches
                kv_page_bytes=self.kv_page_bytes,
                kv_bytes_in_use=self.pool.bytes_in_use,
                kv_page_layers=self.spec.page_layers,
                pages_per_token=self.spec.pages_per_token,
                # fraction of admitted full prompt blocks served from
                # resident pages instead of prefill compute
                prefix_hit_rate=(
                    self.sched.stats["prefix_hit_blocks"]
                    / max(1, self.sched.stats["prefix_lookup_blocks"])
                ),
                # prefix-retention tier (docs/SERVING.md §14)
                pool_pages_retained=self.pool.n_retained,
                pool_shards=self._pool_shards,
            )
        return out

    def _has_work(self) -> bool:
        return (self.sched.has_work or bool(self._deferred)
                or (self._runner is not None and self._runner.pending))

    # ------------------------------------------------ the one decode cycle

    def step(self) -> bool:
        if self._runner is not None:
            return self._runner.step()
        if self.spec_k > 1:
            return self._step_spec()
        t0 = time.perf_counter()
        self._cycle += 1
        self._cycle_worked = False
        try:
            return self._step_once(t0)
        finally:
            self._finish_cycle(t0)

    def _step_once(self, t0: float) -> bool:
        with self._phase("schedule"):
            self._service_deferred()
            self._expire()
            if (self.paged and self.faults is not None
                    and self.faults.fires(
                        "forced_preempt", cycle=self._cycle)):
                victim = self._pick_victim()
                if victim is not None:
                    self._preempt(victim)
            if (self.paged and self.faults is not None
                    and self.faults.fires(
                        "evict_storm", cycle=self._cycle)):
                self.pool.reclaim_retained(self.faults.storm_pages)
        if self.paged:
            self._admit_and_prefill()
        else:
            self._admit_exact()
        if not self.sched.active:
            return False
        if self.paged:
            with self._phase("schedule"):
                self._ensure_flush_pages()
                if self.sched.active and self._table_dirty:
                    self.state["caches"] = pg.set_page_tables(
                        self.state["caches"], self._table
                    )
                    self._table_dirty = False
            if not self.sched.active:  # everyone self-preempted under faults
                return False

        if self._use_splitkv_now():
            step_fn = self._step_splitkv
            self.metrics.inc("splitkv_steps")
        else:
            step_fn = self._step
        self._cycle_worked = True
        with self._phase("decode_dispatch"):
            logits, self.state = step_fn(
                self.params, self.state, jnp.asarray(self.tokens)
            )
        # one host sync per cycle: the explicit block_until_ready boundary
        # separates waiting on device compute from the host work around it
        # (the phase breakdown is how host-stall fraction gets measured)
        with self._phase("device_wait"):
            logits = jax.block_until_ready(logits)
            rows = np.array(np.asarray(logits)[:, 0])
        with self._phase("advance"):
            if self.faults is not None:
                for slot, req in list(self.sched.active.items()):
                    if self.faults.fires(
                        "poison_logits", cycle=self._cycle, uid=req.uid,
                        progress=len(req.out_tokens),
                    ):
                        rows[slot] = np.nan
            nxt = np.argmax(rows, axis=-1)
            bad: dict[int, str] = {}
            if self.guard_logits:
                finite = np.isfinite(rows).all(axis=-1)
                for slot in self.sched.active:
                    if not finite[slot]:
                        bad[slot] = "non-finite logits row"
                    elif not 0 <= int(nxt[slot]) < rows.shape[-1]:
                        bad[slot] = f"invalid next token id {int(nxt[slot])}"
            self.metrics.inc("steps")
            if self.paged:
                # occupancy at the cycle peak — post-admission, pre-release:
                # sampling after _advance would miss every request that
                # retires the same cycle it decoded (short workloads read 0)
                self._occupancy.append(self.pool.occupancy)
            self._advance(nxt, time.perf_counter() - t0, bad=bad)
        if (self.paged and self.audit_every
                and self._cycle % self.audit_every == 0):
            self.audit().raise_if_violations()
        return True

    def _finish_cycle(self, t0: float) -> None:
        """Cycle-boundary bookkeeping, run on every exit path of
        :meth:`step` / :meth:`_step_spec`: fold the per-phase accumulator
        into the registry histograms, derive the device-idle gap, advance
        the first-work -> last-work window behind the ``wall_s`` fallback,
        and service the periodic metrics sink."""
        now = time.perf_counter()
        cycle_s = now - t0
        acc, self._phase_acc = self._phase_acc, {}
        m = self.metrics
        m.observe("cycle_s", cycle_s)
        for name, hist in PHASE_METRICS.items():
            if name in acc:
                m.observe(hist, acc[name])
        # the device is busy (at most) while the host waits on it or runs a
        # prefill; the rest of the cycle is host-side gap the async runtime
        # (ROADMAP) exists to overlap away
        busy = acc.get("device_wait", 0.0) + acc.get("prefill", 0.0)
        m.observe("device_idle_gap_s", max(0.0, cycle_s - busy))
        if self._cycle_worked:
            if self._work_t0 is None:
                self._work_t0 = t0
            self._work_t1 = now
        if self.tracer is not None:
            self.tracer.complete("cycle", t0=t0, dur_s=cycle_s, cat="engine",
                                 args={"cycle": self._cycle})
        if self.metrics_every and self._cycle % self.metrics_every == 0:
            if self.metrics_sink is not None:
                self.metrics_sink(m.snapshot())
            else:
                print(m.to_prometheus(), end="")

    # ------------------------------------------- the speculative decode cycle

    def _step_spec(self) -> bool:
        """One self-speculative cycle (``spec_k > 1``, docs/SERVING.md §11):
        the same lifecycle skeleton as :meth:`step` (deferred releases,
        expiry, forced-preempt fault, admission), then

        1. build the ``[slots, spec_k]`` feed matrix: column 0 is each lane's
           committed next token; replay lanes (teacher forcing) take their
           recorded history, normal lanes leave room for draft candidates;
        2. pre-allocate every flush destination the cycle can reach
           (``_ensure_flush_pages`` with per-lane lookahead — COW and
           preemption semantics unchanged, just applied over a window);
        3. draft pass (one device call): ``spec_k - 1`` greedy steps against
           the truncated ``spec_bits`` read of the same pools, state
           discarded;
        4. verify pass (one device call): a full-fidelity masked scan over
           all feeds — a lane freezes the moment its draft diverges from the
           verify argmax;
        5. host accounting (:meth:`_advance_spec`): accept the longest
           matching prefix, fall back to the verify token at the first
           divergence, preserve the sequential EOS / budget / poisoned-step
           retirement semantics token by token.

        Two host syncs per cycle regardless of ``spec_k`` — the latency win
        on the memory-bound decode this paper targets."""
        t0 = time.perf_counter()
        self._cycle += 1
        self._cycle_worked = False
        try:
            return self._step_spec_once(t0)
        finally:
            self._finish_cycle(t0)

    def _step_spec_once(self, t0: float) -> bool:
        with self._phase("schedule"):
            self._service_deferred()
            self._expire()
            if (self.paged and self.faults is not None
                    and self.faults.fires(
                        "forced_preempt", cycle=self._cycle)):
                victim = self._pick_victim()
                if victim is not None:
                    self._preempt(victim)
            if (self.paged and self.faults is not None
                    and self.faults.fires(
                        "evict_storm", cycle=self._cycle)):
                self.pool.reclaim_retained(self.faults.storm_pages)
        if self.paged:
            self._admit_and_prefill()
        else:
            self._admit_exact()
        if not self.sched.active:
            return False

        k = self.spec_k
        feeds = np.zeros((self.slots, k), np.int32)
        limit = np.zeros((self.slots,), np.int32)
        forced = np.zeros((self.slots,), bool)
        with self._phase("schedule"):
            lookahead: dict[int, int] = {}
            for slot, req in self.sched.active.items():
                feeds[slot, 0] = self.tokens[slot, 0]
                if req.replay_left > 0:
                    # teacher-forced replay: feed recorded history, accept all
                    n = min(k, req.replay_left)
                    start = len(req.out_tokens) - req.replay_left
                    for j in range(1, n):
                        feeds[slot, j] = req.out_tokens[start + j]
                    limit[slot] = n
                    forced[slot] = True
                else:
                    limit[slot] = min(
                        k, req.max_new_tokens - len(req.out_tokens)
                    )
                lookahead[slot] = int(limit[slot])

            if self.paged:
                self._ensure_flush_pages(lookahead=lookahead)
                if self.sched.active:
                    for slot in range(self.slots):
                        if self.sched.active.get(slot) is None:
                            limit[slot] = 0  # preempted mid-ensure: feed nothing
                    if self._table_dirty:
                        self.state["caches"] = pg.set_page_tables(
                            self.state["caches"], self._table
                        )
                        self._table_dirty = False
        if self.paged and not self.sched.active:
            return False  # everyone self-preempted under faults

        self._cycle_worked = True
        if any(limit[s] > 1 and not forced[s]
               for s, _ in self.sched.active.items()):
            with self._phase("decode_dispatch"):
                draft_dev = self._draft(
                    self.params, self.state, jnp.asarray(feeds[:, 0])
                )
            with self._phase("device_wait"):
                drafts = np.asarray(jax.block_until_ready(draft_dev))
            if self.tracer is not None:
                self.tracer.instant("spec_draft", args={"cycle": self._cycle})
            for slot, req in self.sched.active.items():
                n = int(limit[slot])
                if forced[slot] or n <= 1:
                    continue
                feeds[slot, 1:n] = drafts[slot, : n - 1]

        with self._phase("decode_dispatch"):
            v, applied, finite, self.state = self._verify(
                self.params, self.state, jnp.asarray(feeds),
                jnp.asarray(limit), jnp.asarray(forced),
            )
        # host sync: the verify results pull (the only other sync is the
        # draft pull above — 2 per cycle for up to spec_k tokens per lane)
        with self._phase("device_wait"):
            v, applied, finite = jax.block_until_ready((v, applied, finite))
            v = np.asarray(v)
            applied = np.asarray(applied)
            finite = np.asarray(finite)
        with self._phase("advance"):
            poison: set[int] = set()
            if self.faults is not None:
                for slot, req in list(self.sched.active.items()):
                    if self.faults.fires(
                        "poison_logits", cycle=self._cycle, uid=req.uid,
                        progress=len(req.out_tokens),
                    ):
                        poison.add(slot)
            self.metrics.inc("steps")
            self.metrics.inc("spec_cycles")
            if self.paged:
                # occupancy at the cycle peak (post-admission, pre-release)
                self._occupancy.append(self.pool.occupancy)
            self._advance_spec(
                feeds, v, applied, finite, limit, forced,
                time.perf_counter() - t0, poison,
            )
        if (self.paged and self.audit_every
                and self._cycle % self.audit_every == 0):
            self.audit().raise_if_violations()
        return True

    def _advance_spec(self, feeds, v, applied, finite, limit, forced,
                      dt: float, poison: set[int]) -> None:
        """Per-lane accounting for a speculative cycle.  ``applied[slot]``
        marks the feeds the verify scan actually ran (the lane was alive),
        so ``n_ap`` applied feeds mean: feed 0 (committed) plus ``n_ap - 1``
        accepted draft tokens.  Every applied feed is recorded exactly as
        ``spec_k`` sequential cycles would record it; the lane's next
        committed token is the verify argmax after its last applied feed —
        the verify token at first divergence, or the continuation after full
        acceptance.  Emission stops early (and retires ERRORED) at the first
        non-finite verify row, matching the sequential poisoned-step
        semantics: the token that *produced* the bad row is still recorded.
        """
        now = time.perf_counter()
        cyc_drafted = cyc_accepted = 0
        for slot, req in list(self.sched.active.items()):
            n_ap = int(applied[slot].sum())
            if n_ap == 0:
                continue
            if req.replay_left > 0:
                # replay lanes ignore logits entirely (teacher forcing)
                req.pos += n_ap
                req.replay_left -= n_ap
                if req.replay_left > 0:
                    idx = len(req.out_tokens) - req.replay_left
                    self.tokens[slot, 0] = req.out_tokens[idx]
                else:
                    # replay complete: resume the parked unpreempted stream
                    self.tokens[slot, 0] = req.pending_token
                    req.pending_token = None
                    if self.tracer is not None:
                        self.tracer.instant(
                            "replay_done", uid=req.uid, cat="request"
                        )
                continue
            drafted = max(0, int(limit[slot]) - 1)
            accepted = n_ap - 1
            cyc_drafted += drafted
            cyc_accepted += accepted
            self.metrics.inc("spec_draft_tokens", drafted)
            self.metrics.inc("spec_accepted_tokens", accepted)
            self.metrics.inc("spec_rejected_tokens", drafted - accepted)
            req.spec_accepted += accepted
            req.spec_rejected += drafted - accepted

            n_emit = n_ap
            err_reason = None
            if slot in poison:
                # injected fault poisons the cycle's logits: sequential
                # semantics record the fed token, then retire ERRORED
                n_emit = 1
                err_reason = "non-finite logits row"
            elif self.guard_logits:
                bad_idx = np.flatnonzero(~finite[slot, :n_ap])
                if bad_idx.size:
                    n_emit = int(bad_idx[0]) + 1
                    err_reason = "non-finite logits row"
            per_tok = dt / max(1, n_emit)
            retired = False
            for j in range(n_emit):
                tok = int(feeds[slot, j])
                req.out_tokens.append(tok)
                req.pos += 1
                req.token_latencies_s.append(per_tok)
                self._observe_token(req, per_tok, now)
                self.metrics.inc("decoded_tokens")
                if err_reason is not None and j == n_emit - 1:
                    self._retire(
                        req, Phase.ERRORED,
                        reason=(
                            f"request {req.uid} step {self._cycle}: "
                            f"{err_reason}"
                        ),
                    )
                    retired = True
                    break
                hit_eos = self.eos_id is not None and tok == self.eos_id
                if hit_eos or len(req.out_tokens) >= req.max_new_tokens:
                    if not hit_eos:
                        self.metrics.inc("budget_retired")
                    self._retire(req, Phase.DONE)
                    retired = True
                    break
            if not retired:
                self.tokens[slot, 0] = int(v[slot, n_emit - 1])
        if self.tracer is not None:
            self.tracer.instant(
                "spec_verify",
                args={"drafted": cyc_drafted, "accepted": cyc_accepted},
            )

    def _advance(self, nxt: np.ndarray, dt: float,
                 bad: dict[int, str] | None = None) -> None:
        """Shared per-token accounting for every family: record the decoded
        token, advance ``req.pos`` (this step appended its KV), retire on
        EOS or the token budget — budget-capped retirement counts
        ``budget_retired`` exactly once.  Slots in ``bad`` (poisoned step:
        non-finite logits row, invalid token id) retire ERRORED instead —
        isolation, not propagation: every other slot advances normally.

        A rematerializing request (``replay_left > 0``) is teacher-forced:
        the step's KV append is the point (``pos`` advances), its logits are
        ignored (the next token is recorded, not sampled), and nothing is
        re-counted as decoded output."""
        now = time.perf_counter()
        for slot, req in list(self.sched.active.items()):
            self._advance_one(
                slot, req, int(nxt[slot]), (bad or {}).get(slot), dt, now
            )

    def _advance_one(self, slot: int, req: Request, nxt_tok: int,
                     bad: str | None, dt: float, now: float,
                     *, cycle: int | None = None) -> None:
        """One slot's share of :meth:`_advance` — the single per-token
        accounting path both runtimes share: the sync cycle calls it per
        active slot right after its host sync, the async runtime calls it at
        the consumption boundary with the step's dispatch ``cycle`` (for
        error attribution) and its device-computed next token/finite flag.
        Keeping one body is what makes the async token stream bitwise
        identical to the oracle by construction."""
        if req.replay_left > 0:
            req.pos += 1
            req.replay_left -= 1
            if req.replay_left > 0:
                idx = len(req.out_tokens) - req.replay_left
                self.tokens[slot, 0] = req.out_tokens[idx]
            else:
                # replay complete: resume the parked unpreempted stream
                self.tokens[slot, 0] = req.pending_token
                req.pending_token = None
                if self.tracer is not None:
                    self.tracer.instant(
                        "replay_done", uid=req.uid, cat="request"
                    )
            return
        tok = int(self.tokens[slot, 0])
        req.out_tokens.append(tok)
        req.pos += 1
        req.token_latencies_s.append(dt)
        self._observe_token(req, dt, now)
        self.metrics.inc("decoded_tokens")
        if bad is not None:
            step_no = self._cycle if cycle is None else cycle
            self._retire(
                req, Phase.ERRORED,
                reason=f"request {req.uid} step {step_no}: {bad}",
            )
            return
        hit_eos = self.eos_id is not None and tok == self.eos_id
        if hit_eos or len(req.out_tokens) >= req.max_new_tokens:
            if not hit_eos:
                self.metrics.inc("budget_retired")
            self._retire(req, Phase.DONE)
        else:
            self.tokens[slot, 0] = int(nxt_tok)

    def _observe_token(self, req: Request, per_tok_s: float,
                       now: float) -> None:
        """TTFT/TPOT split: a request's first-ever emitted token observes
        submission-to-first-token latency (TTFT, queue wait included, on the
        real clock — never the injectable TTL ``clock``); every later token
        observes the amortized inter-token latency of its cycle (TPOT)."""
        if req.t_first_token_s is None:
            req.t_first_token_s = now
            base = req.t_submit_s
            ttft = (now - base) if base is not None else per_tok_s
            self._ttft_s.append(ttft)
            self.metrics.observe("ttft_s", ttft)
        else:
            self._tpot_s.append(per_tok_s)
            self.metrics.observe("tpot_s", per_tok_s)

    # ---------------------------------------- retirement, expiry, preemption

    def _retire(self, req: Request, phase: Phase,
                reason: str | None = None) -> None:
        """Single retirement path for every terminal phase: reset the
        page-table row to scratch, honor an injected delayed-release fault
        (the pages stay held by the retired uid until serviced), release
        through the scheduler, bump the per-phase stat, and — async runtime
        — hand the finished request to the background completion thread."""
        if self._runner is not None and req.slot is not None:
            # drop dispatch-frontier mirrors; lagging in-flight steps for
            # this slot are discarded at consumption (admit_seq mismatch)
            self._runner.on_slot_cleared(req.slot)
        if self.paged and req.slot is not None:
            self._table[req.slot, :] = req.slot  # stale entries -> scratch
            self._table_dirty = True
        if (self.paged and self.faults is not None and req.pages
                and self.faults.fires(
                    "delayed_release", cycle=self._cycle, uid=req.uid
                )):
            self._deferred.append(
                (self._cycle + self.faults.delay_cycles, req.uid,
                 list(req.pages))
            )
            req.pages = []  # scheduler releases reservation + slot only
        self.sched.retire(req, phase, reason=reason)
        stat = {
            Phase.EXPIRED: "expired", Phase.CANCELLED: "cancelled",
            Phase.ERRORED: "errored",
        }.get(phase)
        if stat is not None:
            self.metrics.inc(stat)
        if phase is Phase.DONE and req.t_submit_s is not None:
            e2e = time.perf_counter() - req.t_submit_s
            self._e2e_s.append(e2e)
            self.metrics.observe("e2e_latency_s", e2e)
        if self.tracer is not None:
            self.tracer.end_open(uid=req.uid, cat="request")
            self.tracer.instant(
                phase.value, uid=req.uid, cat="request",
                args={"reason": reason} if reason is not None else None,
            )
        if self._completions is not None:
            self.metrics.inc("completions_enqueued")
            self._completions.put(req)

    def _service_deferred(self) -> None:
        """Free pages whose injected release delay has elapsed."""
        if not self._deferred:
            return
        due = [d for d in self._deferred if d[0] <= self._cycle]
        self._deferred = [d for d in self._deferred if d[0] > self._cycle]
        for _ready, uid, pages in due:
            for page in pages:
                self.pool.free(page, owner=uid)

    def _expire(self) -> None:
        """Retire every live request whose ``deadline_s`` TTL has passed."""
        now = self.clock()
        for req in self.sched.expired(now):
            if req.phase == Phase.WAITING:
                self.sched.waiting.remove(req)
            self._retire(
                req, Phase.EXPIRED,
                reason=(
                    f"request {req.uid}: deadline_s={req.deadline_s} "
                    "exceeded before completion"
                ),
            )

    def _pick_victim(self, exclude: Request | None = None) -> Request | None:
        """Victim for preemption: an active DECODE-phase request admitted in
        an *earlier* cycle (same-cycle admissions are mid-adoption — their
        prefill splice must not be torn down underneath them).  Policy
        ``"youngest"`` preempts the latest admission (FIFO fairness: the
        last one in yields first); ``"fewest_pages"`` the cheapest
        rematerialization, ties to the youngest."""
        cands = [
            r for r in self.sched.active.values()
            if r is not exclude and r.phase == Phase.DECODE
            and r.admit_cycle < self._cycle
        ]
        if not cands:
            return None
        if self.preempt_policy == "fewest_pages":
            return min(cands, key=lambda r: (len(r.pages), -r.admit_seq))
        return max(cands, key=lambda r: r.admit_seq)

    def _preempt(self, req: Request) -> None:
        """Preempt-by-rematerialization (docs/SERVING.md §10): park the
        decoded-but-unfed next token, reset the table row, and hand the
        request to the scheduler, which queues its decoded tokens for
        teacher-forced replay and requeues it at the FIFO head.

        A victim caught *mid-replay* (preempted again before its previous
        rematerialization finished) keeps its originally parked token — the
        token currently in the feed buffer is a replayed one, already in
        ``out_tokens``."""
        slot = req.slot
        if self._runner is not None:
            # resolve a still-lazy admission feed into the host mirror (the
            # parked token must be a real value) and drop dispatch mirrors
            self._runner.on_preempt(req)
        if req.replay_left > 0:
            pending = req.pending_token
        else:
            pending = int(self.tokens[slot, 0])
        self._table[slot, :] = slot
        self._table_dirty = True
        self.metrics.inc("preempted")
        self.metrics.inc("preempt_remat_tokens", len(req.out_tokens))
        if self.tracer is not None:
            self.tracer.end_open(uid=req.uid, cat="request")
            self.tracer.instant(
                "preempt", uid=req.uid, cat="request",
                args={"tokens_to_replay": len(req.out_tokens)},
            )
            self.tracer.begin("queue", uid=req.uid, cat="request")
        self.sched.preempt(req, pending_token=pending)

    def _use_splitkv_now(self) -> bool:
        if self._step_splitkv is None or self.splitkv == "never":
            return False
        if self.splitkv == "always":
            return True
        if self.page_affine:
            # sharded pool storage: the plain step would gather every
            # shard's pages to every chip — the sharded walk is the point
            return True
        axis_size = int(self.mesh.shape[self.splitkv_axis])
        if axis_size <= 1:
            return False
        active = self.sched.active.values()
        max_blocks = max((r.pos // self.block_n for r in active), default=0)
        cores = bd_ops.default_splitkv_cores()
        return (
            len(self.sched.active) * self._h_kv < cores
            and max_blocks >= 2 * axis_size
        )

    # ----------------------------------------------------- paged admission

    def _alloc_page(self, req: Request, *, admission: bool = False,
                    block: int | None = None) -> int | None:
        """Pool alloc charged to ``req``: converts one of its reservation
        units and joins its page list.

        Under ``reserve_policy="worst_case"`` the reservation always covers
        the alloc (the preempt-free guarantee, unchanged).  Under
        ``"expected"`` a request that outlives its expectation arrives here
        with ``reserved_pages == 0`` and must *extend* one unit — when the
        commitment budget is full, a victim is preempted per
        ``preempt_policy``; with no eligible victim the requester preempts
        *itself* (returns None; the caller skips — the request is already
        requeued).  Admission-time allocs never extend: ``reserve_need``
        floors the reservation at the prompt's own block count, so
        preemption can only fire on the decode flush path.

        Retention ordering: ``pool.reserve``/``pool.alloc`` drain the
        RETAINED tier (LRU) before reporting pressure, so every retained
        page is reclaimed before any victim is preempted here.

        ``block`` (page-affine mode) pins the page to the shard owning
        that table column; when the shard is dry — free list empty *and*
        no retained page in the shard — victims are preempted until one
        of their pages refills it (or the requester self-preempts).

        An injected ``alloc_fail`` fault exercises the same victim path
        deterministically (the alloc itself then proceeds — recovery, not
        crash, is what the fault probes)."""
        if (self.faults is not None
                and self.faults.fires(
                    "alloc_fail", cycle=self._cycle, uid=req.uid
                )):
            victim = self._pick_victim(exclude=req)
            if victim is not None:
                self._preempt(victim)
            elif not admission and req.reserved_pages <= 0:
                self._preempt(req)
                return None
        if req.reserved_pages <= 0:
            while not self.pool.reserve(1, owner=req.uid):
                victim = self._pick_victim(exclude=req)
                if victim is None:
                    self._preempt(req)
                    return None
                self._preempt(victim)
            req.reserved_pages += 1
        shard = None
        if self.page_affine and block is not None:
            shard = block // self._nb_local
            while not self.pool.shard_available(shard):
                victim = self._pick_victim(exclude=req)
                if victim is None:
                    if admission:
                        # mid-splice: the bucket adoption cannot be torn
                        # down cleanly — full per-shard provisioning (the
                        # affine default) makes this unreachable
                        raise RuntimeError(
                            f"page-affine shard {shard} exhausted at "
                            f"admission of request {req.uid} with no "
                            "preemptible victim"
                        )
                    self._preempt(req)
                    return None
                self._preempt(victim)
        page = self.pool.alloc(owner=req.uid, shard=shard)
        req.reserved_pages -= 1
        req.pages.append(page)
        return page

    def _splice_side_state(self, dstate, slot_ids) -> list[str]:
        """Copy the declared dense side-state (``PagedSpec.side_state`` —
        e.g. HybridLM's SSM recurrent states) of just-prefilled rows into
        their decode slots (prefill row ``r`` -> slot ``slot_ids[r]``); the
        page table never sees these pytrees.  Returns the top-level state
        keys handled (the shim skips them in its generic splice)."""
        if not self.spec.side_state:
            return []
        sidx = jnp.asarray(slot_ids, jnp.int32)
        rows = jnp.arange(len(slot_ids), dtype=jnp.int32)
        handled = []
        for path, bdim in self.spec.side_state:
            dst = get_path(self.state, path)
            src = get_path(dstate, path)

            def put(d, s):
                idx = [slice(None)] * d.ndim
                idx[bdim] = sidx
                src_idx = [slice(None)] * s.ndim
                src_idx[bdim] = rows
                return d.at[tuple(idx)].set(
                    s[tuple(src_idx)].astype(d.dtype))

            set_path(self.state, path, jax.tree.map(put, dst, src))
            handled.append(path.split("/")[0])
        return handled

    def _admit_and_prefill(self, *, defer_first: bool = False) -> dict:
        with self._phase("schedule"):
            groups = self.sched.admit()
            if groups:
                self._note_admissions(groups)
        lazy: dict[int, tuple] = {}
        for bucket_len, reqs in groups.items():
            with self._phase("prefill"):
                lazy.update(self._prefill_bucket(
                    bucket_len, reqs, defer_first=defer_first
                ))
        return lazy

    def _note_admissions(self, groups: dict[int, list[Request]]) -> None:
        """Per-request admission telemetry: close the queue span, open the
        prefill span, and observe queue wait — first admission only, so a
        preemption re-admission never double-counts the same request."""
        now = time.perf_counter()
        for reqs in groups.values():
            for req in reqs:
                first_admit = req.t_admit_s is None
                req.t_admit_s = now
                if first_admit and req.t_submit_s is not None:
                    qw = now - req.t_submit_s
                    self._queue_wait_s.append(qw)
                    self.metrics.observe("queue_wait_s", qw)
                if self.tracer is not None:
                    self.tracer.end_open(uid=req.uid, cat="request")
                    self.tracer.begin("prefill", uid=req.uid, cat="request")

    def _prefill_bucket(self, bucket_len: int, reqs: list[Request],
                        *, defer_first: bool = False) -> dict:
        # divergent-suffix prefill: row r holds request r's unshared tail
        toks = np.zeros((self.slots, bucket_len), np.int32)
        lens = np.ones((self.slots,), np.int32)  # pad rows: length 1
        shared_blocks = [len(r.shared_pages) for r in reqs]
        p_max = max(shared_blocks)
        for r, req in enumerate(reqs):
            sl = req.suffix_len(self.block_n)
            toks[r, :sl] = req.prompt[len(req.shared_pages) * self.block_n :]
            lens[r] = sl
            self.metrics.inc("prefill_tokens", sl)
            self.metrics.inc("prefill_tokens_saved", req.prompt_len - sl)
        if self.spec.exact_prefill:
            # all admitted rows carry exactly bucket_len real tokens —
            # recurrent side-state tolerates no right-padding, and the
            # model's prefill returns last-token logits directly
            logits, dstate = self._prefill(self.params, jnp.asarray(toks))
        elif p_max == 0:
            logits, dstate = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lens)
            )
        else:
            # pad the prior-page walk to a power-of-two block count so
            # the jit cache keys on (bucket_len, prior bucket) only
            p_pad = bucket_for(p_max, min_bucket=1)
            pages = np.zeros((self.slots, p_pad), np.int32)
            plens = np.zeros((self.slots,), np.int32)
            for r, req in enumerate(reqs):
                s = len(req.shared_pages)
                pages[r, :s] = req.shared_pages
                plens[r] = s * self.block_n
            logits, dstate = self._prefill_shared(
                self.params, self.state["caches"], jnp.asarray(toks),
                jnp.asarray(lens), jnp.asarray(pages), jnp.asarray(plens),
            )
        self.metrics.inc("prefill_calls")
        lazy: dict[int, tuple] = {}
        if defer_first:
            # async runtime: the first token stays a device array — no host
            # sync at admission; the scalar is resolved lazily at the slot's
            # first consumption boundary (or at preemption)
            first_dev = jnp.argmax(logits[:, 0], axis=-1)
            first = None
        else:
            first = np.argmax(np.asarray(logits)[:, 0], axis=-1)

        slot_ids, lengths, pages_per_req = [], [], []
        for r, req in enumerate(reqs):
            s = len(req.shared_pages)
            sl = req.suffix_len(self.block_n)
            n_blocks = sl // self.block_n
            # covered by the reservation floor — never preempts here;
            # page-affine: fresh block j lands at table column s + j
            pgs = [
                self._alloc_page(req, admission=True, block=s + j)
                for j in range(n_blocks)
            ]
            self._table[req.slot, :] = req.slot  # fresh scratch row
            self._table[req.slot, :s] = req.shared_pages
            if req.spec_page is not None:
                # speculative flush destination (COW candidate)
                self._table[req.slot, s] = req.spec_page
            self._table[req.slot, s : s + n_blocks] = pgs
            slot_ids.append(req.slot)
            lengths.append(sl)
            pages_per_req.append(pgs)
            req.phase = Phase.DECODE
            req.pos = req.prompt_len
            req.admit_cycle = self._cycle
            if self.tracer is not None:
                self.tracer.end("prefill", uid=req.uid, cat="request")
                self.tracer.begin("decode", uid=req.uid, cat="request")
            if req.replay_left > 0:
                # rematerializing victim: teacher-force its recorded
                # decode stream (first replayed token now, the rest in
                # `_advance`) — rebuilding the decode-built cache blocks
                # through the decode path keeps them bitwise identical
                self.tokens[req.slot, 0] = req.out_tokens[0]
            elif req.pending_token is not None:
                # preempted before any decode: resume from the parked
                # decoded-but-unfed token, not the re-prefill's argmax
                self.tokens[req.slot, 0] = req.pending_token
                req.pending_token = None
            elif defer_first:
                lazy[req.slot] = (first_dev, r)
            else:
                self.tokens[req.slot, 0] = int(first[r])
        self._table_dirty = True
        self.state["caches"] = pg.adopt_prefill(
            self.state["caches"], dstate["caches"],
            slot_ids=slot_ids, lengths=lengths,
            pages_per_req=pages_per_req, block_n=self.block_n,
            base_blocks=shared_blocks,
        )
        self._splice_side_state(dstate, slot_ids)
        sidx = jnp.asarray(slot_ids, jnp.int32)
        self.state["pos"] = self.state["pos"].at[sidx].set(
            jnp.asarray([r.prompt_len for r in reqs], jnp.int32)
        )
        # full prompt blocks (shared + fresh) become discoverable for
        # later admissions — content is committed by the adoption above
        for r, req in enumerate(reqs):
            self.sched.register_prefix(
                req, req.shared_pages + pages_per_req[r]
            )
        return lazy

    def _ensure_flush_pages(
        self, lookahead: dict[int, int] | None = None, pos_of=None
    ) -> None:
        """Allocate the destination page for every sequence whose residual
        fills on the upcoming step (pos % block_n == block_n - 1): the flush
        will commit packed block pos // block_n through the page table.

        ``lookahead`` (slot -> feed count, speculative cycles) widens the
        check to every position the cycle can reach — a ``spec_k``-token
        verify scan may cross multiple block boundaries, and each needs its
        destination (fresh page / COW replica) resolved before the table is
        pushed.  ``None`` keeps the sequential single-step window.

        Copy-on-write: when the destination column already holds a pool page
        with refcount > 1 (a speculative shared tail — serve/scheduler.py),
        the flush must not be visible to the other holders.  The request
        gets a private page (covered by its reservation: spec-tail pages are
        never discounted at admission), the packed block is replicated
        device-side (``pages.cow_pages``), and only this request's table
        column is repointed before the flush commits over the replica.

        This is the one place preemption can fire (``_alloc_page`` under the
        expected reservation policy), so the iteration snapshots the active
        set and re-checks each slot: a request preempted by an earlier
        allocation this cycle (or that preempted *itself* — alloc returned
        None) is skipped, its table row already reset to scratch.

        ``pos_of`` (request -> position) overrides the position the check
        runs at: the async runtime passes its dispatch-frontier position,
        which runs ahead of ``req.pos`` (consumption truth) by the in-flight
        window — destinations must exist before the step that flushes them
        is *dispatched*, not consumed."""
        cow_src, cow_dst = [], []
        for req in list(self.sched.active.values()):
            pos = req.pos if pos_of is None else pos_of(req)
            window = 1 if lookahead is None else lookahead.get(req.slot, 1)
            for j in range(max(1, window)):
                if self.sched.active.get(req.slot) is not req:
                    break  # preempted by an earlier alloc this cycle
                if (pos + j) % self.block_n != self.block_n - 1:
                    continue
                blk = (pos + j) // self.block_n
                entry = int(self._table[req.slot, blk])
                if entry < self.slots:  # still scratch -> fresh private page
                    page = self._alloc_page(req, block=blk)
                    if page is None:
                        continue  # self-preempted: requeued, row reset
                    self._table[req.slot, blk] = page
                    self._table_dirty = True
                elif self.pool.refcount(entry) > 1:  # shared -> copy-on-write
                    # page-affine: src and dst both back column blk, so the
                    # replica stays in the shard that owns the column
                    page = self._alloc_page(req, block=blk)
                    if page is None:
                        continue  # self-preempted: requeued, row reset
                    cow_src.append(entry)
                    cow_dst.append(page)
                    req.pages.remove(entry)
                    if req.spec_page == entry:
                        req.spec_page = None
                    self.pool.free(entry, owner=req.uid)
                    self._table[req.slot, blk] = page
                    self._table_dirty = True
                    self.metrics.inc("cow_copies")
                    if self.tracer is not None:
                        self.tracer.instant(
                            "cow", uid=req.uid, cat="request",
                            args={"src": entry, "dst": page},
                        )
                else:
                    # privately held page (last sharer left): the flush will
                    # overwrite it in place — drop any stale index node first
                    self.sched.forget_page(entry)
        if cow_src:
            self.state["caches"] = pg.cow_pages(
                self.state["caches"], cow_src, cow_dst
            )

    # ------------------------------------------------- exact-length shim

    def _admit_exact(self, *, defer_first: bool = False) -> dict:
        """Shim admission for dense-state models: the same scheduler (pool-
        less, exact-length groups), one per-request exact-length prefill
        spliced into the batched state."""
        with self._phase("schedule"):
            groups = self.sched.admit()
            if groups:
                self._note_admissions(groups)
        lazy: dict[int, tuple] = {}
        for reqs in groups.values():
            for req in reqs:
                with self._phase("prefill"):
                    lazy.update(
                        self._fill_slot(req, defer_first=defer_first)
                    )
        return lazy

    def _fill_slot(self, req: Request, *, defer_first: bool = False) -> dict:
        i = req.slot
        batch = {"tokens": jnp.asarray(req.prompt[None], jnp.int32)}
        logits, st = self._prefill(self.params, batch)

        # declared recurrent side-state splices on its true batch axis (the
        # same routine the paged admission uses, with one row -> one slot)
        handled = self._splice_side_state(st, [i])

        def splice(dst, src):
            if dst is None:
                return None
            if not isinstance(dst, jax.Array) and not hasattr(dst, "ndim"):
                return dst
            # batch dim: caches are stacked (L, B, ...) -> dim 1; pos -> dim 0
            bdim = 0 if dst.ndim == 1 else 1
            idx = [slice(None)] * dst.ndim
            idx[bdim] = i
            src_idx = [slice(None)] * src.ndim
            src_idx[bdim] = 0
            return dst.at[tuple(idx)].set(src[tuple(src_idx)].astype(dst.dtype))

        for key in self.state:
            if key in handled:
                continue
            self.state[key] = jax.tree.map(splice, self.state[key], st[key])
        lazy: dict[int, tuple] = {}
        if defer_first:
            # scalar device argmax, resolved at the consumption boundary
            lazy[i] = (jnp.argmax(logits[0, -1]), None)
        else:
            self.tokens[i, 0] = int(np.argmax(np.asarray(logits)[0, -1]))
        self.metrics.inc("prefill_calls")
        self.metrics.inc("prefill_tokens", req.prompt_len)
        req.phase = Phase.DECODE
        req.pos = req.prompt_len
        req.admit_cycle = self._cycle
        if self.tracer is not None:
            self.tracer.end("prefill", uid=req.uid, cat="request")
            self.tracer.begin("decode", uid=req.uid, cat="request")
        return lazy
