"""Async overlapped serving runtime (docs/SERVING.md §13).

The synchronous :class:`~repro.serve.engine.ServeEngine` cycle stops the
world once per decoded token: dispatch the jitted step and the greedy
selection behind it, ``block_until_ready``, pull the ``[slots]`` token ids
and finite flags, do all the scheduling bookkeeping, then dispatch again —
the device idles through every host phase, which is exactly the
``host_stall_fraction`` the engine's phase breakdown measures.  This module
restructures that loop around three ideas (the MaxText/JetStream offline
inference pattern):

* **Device-resident token feed, bounded in-flight window.**  The overlapped
  decode step computes the next-token argmax (and a per-row finite flag) *on
  device* — the sync cycle's own ``serve_select_tokens`` body, fused into
  the decode program — and feeds it straight back into the next dispatch:
  no host round trip on the critical path.  Dispatched steps enter a FIFO of at most
  ``window`` in-flight records; the host consumes the *oldest* record (one
  small ``np.asarray`` transfer — the only blocking sync) while up to
  ``window - 1`` younger steps are still computing.  All per-token
  bookkeeping (EOS/budget retirement, replay accounting, poisoned-step
  isolation) runs at this **consumption boundary**, through the same
  ``ServeEngine._advance_one`` body the sync cycle uses — which is why the
  token stream is bitwise identical to the sync oracle by construction.

* **Dispatch-frontier control state.**  Host decisions that must precede a
  dispatch — flush-destination allocation, COW, page-table pushes, prefill
  admission — run against a *dispatch-side* position mirror that leads
  ``req.pos`` (consumption truth) by the in-flight depth.  Retirement is
  discovered late by up to ``window`` steps: the lagging steps decode
  garbage into the request's still-private pages (never shared ones — flush
  destinations are fresh or COW'd), their results are recognized by an
  ``admit_seq`` snapshot mismatch at consumption and discarded
  (``discarded_steps``), and device-order execution guarantees a freed page
  is re-written by its next owner *after* any lagging garbage flush.
  Preemption parks the consumption-frontier feed token (``engine.tokens``),
  so rematerialization replays exactly the sync stream.

* **Background completion thread.**  Terminal requests are handed to a
  :class:`CompletionWorker` through a bounded queue; the worker detokenizes
  and runs the completion callback off the dispatch thread, recording every
  completion exactly once (the no-lost/no-double-completed ledger the
  stress suite asserts).  Every blocking queue operation carries a
  ``watchdog_s`` timeout that raises :class:`DeadlockError` instead of
  wedging — a hung thread fails fast, in tests and in CI.

Admission never syncs either: the bucketed prefill's first-token argmax
stays a device array (``defer_first=True``), scattered into the device feed
buffer and resolved on host lazily — at the slot's first consumption
boundary, or eagerly if the request is preempted before that.

The decode executable is AOT-compiled at construction against the engine's
real decode-state avals with the state and token buffers donated
(``donate_argnums``), so the steady-state loop never retraces and recycles
its buffers in place where the backend supports donation.

Caveat: with ``guard_logits=False`` a ``poison_logits`` fault cannot
reproduce the sync engine's token 0 for a poisoned row (the argmax of an
all-NaN row): the device argmax sees the unpoisoned row and feeds it on
without a host round trip, so bitwise fault parity requires the default
``guard_logits=True`` — the poisoned request retires ERRORED before its
next token is ever used, identically in both runtimes.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import attention as catt
from repro.serve import pages as pg
from repro.serve.engine import serve_select_tokens


class DeadlockError(RuntimeError):
    """A bounded queue operation or the liveness watchdog timed out: the
    overlapped runtime would otherwise deadlock/livelock silently."""


#: feed-plan marker: this dispatch's feed is a not-yet-resolved device-side
#: prefill first-token (see ``AsyncRunner._lazy_first``)
_LAZY = object()

#: completion-queue shutdown sentinel
_SENTINEL = object()


@dataclasses.dataclass(frozen=True)
class CompletionRecord:
    """What the background thread produces per finished request."""

    uid: int
    phase: str          # terminal Phase value ("done", "errored", ...)
    tokens: tuple       # the request's final output token ids
    text: str           # detokenizer output
    error: str | None   # req.error at retirement


class CompletionWorker:
    """Bounded-queue background detokenize/completion thread.

    The engine's single retirement path enqueues every terminal request
    (``ServeEngine._retire``); this thread detokenizes, fires the
    ``on_complete`` callback, and records the completion in a thread-safe
    ledger (``records``: uid -> :class:`CompletionRecord`).  A uid enqueued
    twice increments ``duplicates`` instead of overwriting — the stress
    suite asserts it stays 0.  Callback/detokenizer exceptions are captured
    in ``errors`` and re-raised at :meth:`drain` (the worker itself never
    dies).  ``put`` blocks at most ``watchdog_s`` on a full queue and
    ``drain`` waits at most ``watchdog_s`` for the queue to empty; both
    raise :class:`DeadlockError` on timeout."""

    def __init__(self, *, queue_size: int = 64, watchdog_s: float = 30.0,
                 detokenizer=None, on_complete=None):
        self.watchdog_s = float(watchdog_s)
        self.detokenizer = (
            detokenizer if detokenizer is not None
            else (lambda toks: " ".join(str(t) for t in toks))
        )
        self.on_complete = on_complete
        self._q: queue.Queue = queue.Queue(maxsize=max(1, int(queue_size)))
        self._lock = threading.Lock()
        self.records: dict[int, CompletionRecord] = {}
        self.duplicates = 0
        self.errors: list[Exception] = []
        self._closed = False
        self._thread = threading.Thread(
            target=self._loop, name="serve-completions", daemon=True
        )
        self._thread.start()

    @property
    def processed(self) -> int:
        """Completions recorded so far (thread-safe)."""
        with self._lock:
            return len(self.records)

    def put(self, req) -> None:
        """Enqueue a just-retired request (main thread).  The payload is
        snapshotted here — the worker never touches live Request state."""
        item = (req.uid, req.phase.value, tuple(req.out_tokens), req.error)
        try:
            self._q.put(item, timeout=self.watchdog_s)
        except queue.Full:
            raise DeadlockError(
                f"completion queue full for {self.watchdog_s:.1f}s "
                f"(maxsize {self._q.maxsize}): detokenize thread wedged"
            ) from None

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is _SENTINEL:
                self._q.task_done()
                return
            uid, phase, tokens, error = item
            try:
                rec = CompletionRecord(
                    uid=uid, phase=phase, tokens=tokens,
                    text=self.detokenizer(tokens), error=error,
                )
                with self._lock:
                    if uid in self.records:
                        self.duplicates += 1
                    else:
                        self.records[uid] = rec
                if self.on_complete is not None:
                    self.on_complete(rec)
            except Exception as exc:  # surfaced at drain, thread survives
                with self._lock:
                    self.errors.append(exc)
            finally:
                self._q.task_done()

    def drain(self) -> None:
        """Block until every enqueued completion was processed; re-raise the
        first captured worker exception; DeadlockError past watchdog_s."""
        deadline = time.perf_counter() + self.watchdog_s
        while self._q.unfinished_tasks:
            if time.perf_counter() > deadline:
                raise DeadlockError(
                    f"completion queue failed to drain within "
                    f"{self.watchdog_s:.1f}s "
                    f"({self._q.unfinished_tasks} item(s) outstanding)"
                )
            time.sleep(0.001)
        with self._lock:
            if self.errors:
                raise self.errors[0]

    def close(self, timeout: float | None = None) -> None:
        """Stop the worker thread (idempotent)."""
        if self._closed:
            return
        self._closed = True
        self._q.put(_SENTINEL)
        self._thread.join(self.watchdog_s if timeout is None else timeout)


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unconsumed decode step."""

    cycle: int      # engine cycle that dispatched it (error attribution)
    nxt: object     # device [slots] int32: per-slot next-token argmax
    finite: object  # device [slots] bool: per-slot logits-row finiteness
    snap: list      # [(slot, req, admit_seq)] active set at dispatch
    lazy: dict      # slot -> (dev, row, admit_seq): firsts to resolve here
    t0: float       # dispatch wall time (pipeline token latency)


class AsyncRunner:
    """The overlapped decode loop behind ``ServeEngine(async_runtime=True)``.

    One :meth:`step` = consume the oldest in-flight record if the window is
    full, run the scheduling skeleton (deferred releases, expiry, faults,
    admission — prefill dispatches overlap in-flight decode), pre-allocate
    dispatch-frontier flush destinations, then dispatch one more decode step
    without waiting for any of it.  See the module docstring for the
    parity argument."""

    def __init__(self, engine, *, window: int = 2, watchdog_s: float = 30.0):
        if window < 1:
            raise ValueError(f"async window {window} must be >= 1")
        self.eng = engine
        self.window = int(window)
        self.watchdog_s = float(watchdog_s)
        self.inflight: deque[_InFlight] = deque()
        self.dispatched = 0
        self.last_progress = time.perf_counter()
        # dispatch-frontier mirrors (consumption truth lives on the Request)
        self._dispatch_pos: dict[int, int] = {}
        self._feed_plan: dict[int, deque] = {}
        # slot -> (dev_array, row|None, admit_seq): unresolved admission
        # first-tokens; resolved at first consumption or at preemption
        self._lazy_first: dict[int, tuple] = {}
        # entries not yet attached to a dispatch record (exactly one each)
        self._pending_lazy: dict[int, tuple] = {}
        # set when a consumption empties the pipeline, cleared (and observed
        # as device_starved_s) at the next dispatch; None before the first
        # dispatch — filling the pipeline at startup is prefill-bound, not
        # starvation, in both runtimes
        self._idle_since: float | None = None

        model = engine.model
        impl, quant_impl = engine._impl, engine._quant_impl

        def serve_async_decode_step(p, s, t):
            logits, st = model.decode_step(
                p, s, t, impl=impl, quant_impl=quant_impl
            )
            nxt, finite = serve_select_tokens(logits)
            return nxt, finite, nxt[:, None], st

        self._tokens_dev = jnp.zeros((engine.slots, 1), jnp.int32)
        self._astep = jax.jit(serve_async_decode_step, donate_argnums=(1, 2))
        # AOT compile against the engine's real decode-state avals: the
        # executable is warm before the first request arrives, and the
        # steady-state loop never retraces
        self._astep_exe = self._astep.lower(
            engine.params, engine.state, self._tokens_dev
        ).compile()
        self._astep_sk = None  # lazily built cross-chip split-KV variant
        # feed-override helpers (fixed shapes -> one compile each):
        # host-known token overrides merge by mask; admission firsts scatter
        # by row->slot index, padded rows pointing out of bounds (dropped)
        def serve_feed_merge(t, mask, vals):
            return jnp.where(mask[:, None], vals[:, None], t)

        def serve_feed_scatter_rows(t, sidx, vals):
            return t.at[sidx, 0].set(vals)

        def serve_feed_scatter_one(t, slot, val):
            return t.at[slot, 0].set(val)

        self._merge = jax.jit(serve_feed_merge)
        self._scatter_rows = jax.jit(serve_feed_scatter_rows)
        self._scatter_one = jax.jit(serve_feed_scatter_one)

    # ----------------------------------------------------------- liveness

    @property
    def pending(self) -> bool:
        """True while dispatched steps await consumption (drain gate)."""
        return bool(self.inflight)

    def check_liveness(self) -> None:
        """Raise :class:`DeadlockError` when the runtime has work but made
        no progress (dispatch, consumption, retirement) for watchdog_s."""
        if not self.eng._has_work():
            return
        stalled = time.perf_counter() - self.last_progress
        if stalled > self.watchdog_s:
            raise DeadlockError(
                f"async runtime made no progress for {stalled:.1f}s "
                f"(> watchdog_s={self.watchdog_s}): "
                f"{len(self.inflight)} in flight, "
                f"{len(self.eng.sched.active)} active, "
                f"{len(self.eng.sched.waiting)} waiting"
            )

    # ----------------------------------------------------- engine hooks

    def on_slot_cleared(self, slot: int) -> None:
        """Retirement hook: drop the slot's dispatch-frontier mirrors; its
        lagging in-flight steps are discarded at consumption."""
        self._dispatch_pos.pop(slot, None)
        self._feed_plan.pop(slot, None)
        self._lazy_first.pop(slot, None)
        self._pending_lazy.pop(slot, None)
        self.last_progress = time.perf_counter()

    def on_preempt(self, req) -> None:
        """Preemption hook, called before the engine reads the parked token
        from ``engine.tokens``: if the slot's admission first-token is still
        device-side (no consumption reached it yet), resolve it into the
        host mirror now — the parked token must be a concrete value."""
        slot = req.slot
        lazy = self._lazy_first.pop(slot, None)
        if lazy is not None and req.replay_left == 0:
            dev, row, seq = lazy
            if seq == req.admit_seq:
                arr = np.asarray(dev)
                self.eng.tokens[slot, 0] = (
                    int(arr[row]) if row is not None else int(arr)
                )
        self._dispatch_pos.pop(slot, None)
        self._feed_plan.pop(slot, None)
        self._pending_lazy.pop(slot, None)

    # ------------------------------------------------------- the cycle

    def step(self) -> bool:
        return self.eng._run_cycle(self._step_once)

    def _step_once(self, t0: float) -> bool:
        eng = self.eng
        if len(self.inflight) >= self.window:
            self._consume_one()
        with eng._phase("schedule"):
            eng._service_deferred()
            eng._expire()
            if (eng.paged and eng.faults is not None
                    and eng.faults.fires(
                        "forced_preempt", cycle=eng._cycle)):
                victim = eng._pick_victim()
                if victim is not None:
                    eng._preempt(victim)
            if (eng.paged and eng.faults is not None
                    and eng.faults.fires(
                        "evict_storm", cycle=eng._cycle)):
                eng.pool.reclaim_retained(eng.faults.storm_pages)
        # prefill admission overlaps the in-flight decode steps: the bucketed
        # prefill is dispatched (device-ordered behind them) and its first
        # tokens stay on device (defer_first)
        if eng.paged:
            lazy = eng._admit_and_prefill(defer_first=True)
        else:
            lazy = eng._admit_exact(defer_first=True)
        self._register_admissions(lazy)
        if not eng.sched.active:
            return self._drain_progress()
        if eng.paged:
            with eng._phase("schedule"):
                eng._ensure_flush_pages(pos_of=self._frontier_pos)
                if eng.sched.active and eng._table_dirty:
                    eng.state["caches"] = pg.set_page_tables(
                        eng.state["caches"], eng._table
                    )
                    eng._table_dirty = False
            if not eng.sched.active:  # everyone self-preempted under faults
                return self._drain_progress()

        eng._cycle_worked = True
        if eng.paged:
            # occupancy at the cycle peak (post-admission, pre-release)
            eng._occupancy.append(eng.pool.occupancy)
        with eng._phase("decode_dispatch"):
            self._apply_overrides()
            if eng._use_splitkv_now():
                step_fn = self._splitkv_step()
                eng.metrics.inc("splitkv_steps")
            else:
                step_fn = self._astep_exe
            nxt, finite, toks2d, eng.state = step_fn(
                eng.params, eng.state, self._tokens_dev
            )
            self._tokens_dev = toks2d
        now = time.perf_counter()
        if self._idle_since is not None:
            # the dispatch pipeline was empty until now: starved time is the
            # overlap-aware host-stall numerator (docs/OBSERVABILITY.md)
            eng.metrics.observe(
                "device_starved_s", max(0.0, now - self._idle_since)
            )
            self._idle_since = None
        snap = [
            (slot, req, req.admit_seq)
            for slot, req in sorted(eng.sched.active.items())
        ]
        taken, self._pending_lazy = self._pending_lazy, {}
        self.inflight.append(_InFlight(
            cycle=eng._cycle, nxt=nxt, finite=finite, snap=snap,
            lazy=taken, t0=t0,
        ))
        for slot, req, _seq in snap:
            self._dispatch_pos[slot] = (
                self._dispatch_pos.get(slot, req.pos) + 1
            )
        self.dispatched += 1
        self.last_progress = now
        return True

    def _drain_progress(self) -> bool:
        """Nothing to dispatch: consume one in-flight record if any."""
        if self.inflight:
            self._consume_one()
            return True
        return False

    def _frontier_pos(self, req) -> int:
        return self._dispatch_pos.get(req.slot, req.pos)

    def _register_admissions(self, lazy: dict) -> None:
        """Set up dispatch-frontier mirrors for slots admitted this cycle:
        the dispatch position starts at the prompt length and the feed plan
        holds every host-known feed the slot consumes before switching to
        the device next-token chain — the whole teacher-forced replay stream
        plus the parked token for a rematerializing victim, the parked token
        alone for a pre-decode preemptee, the lazy device first otherwise."""
        eng = self.eng
        for slot, req in eng.sched.active.items():
            if slot in self._dispatch_pos:
                continue
            self._dispatch_pos[slot] = req.pos
            plan: deque = deque()
            if req.replay_left > 0:
                plan.extend(req.out_tokens)
                plan.append(req.pending_token)
            elif slot in lazy:
                dev, row = lazy[slot]
                entry = (dev, row, req.admit_seq)
                self._lazy_first[slot] = entry
                self._pending_lazy[slot] = entry
                plan.append(_LAZY)
            else:
                plan.append(int(eng.tokens[slot, 0]))
            self._feed_plan[slot] = plan

    def _apply_overrides(self) -> None:
        """Fold this dispatch's feed overrides into the device token buffer:
        one entry pops off each planned slot's feed queue (host-known values
        merge by mask; unresolved admission firsts scatter device-to-device,
        padded scatter rows point out of bounds and drop)."""
        eng = self.eng
        host_mask = np.zeros((eng.slots,), bool)
        host_vals = np.zeros((eng.slots,), np.int32)
        any_host = False
        groups: dict[int, tuple] = {}  # id(dev) -> (dev, [(slot, row)])
        scalars: list[tuple] = []
        for slot in list(self._feed_plan):
            if eng.sched.active.get(slot) is None:
                continue
            plan = self._feed_plan[slot]
            if not plan:
                self._feed_plan.pop(slot, None)
                continue
            val = plan.popleft()
            if not plan:
                self._feed_plan.pop(slot, None)
            if val is _LAZY:
                entry = self._lazy_first.get(slot)
                if entry is None:
                    continue
                dev, row, _seq = entry
                if row is None:
                    scalars.append((slot, dev))
                else:
                    key = id(dev)
                    groups.setdefault(key, (dev, []))[1].append((slot, row))
            else:
                host_mask[slot] = True
                host_vals[slot] = int(val)
                any_host = True
        if any_host:
            self._tokens_dev = self._merge(
                self._tokens_dev, jnp.asarray(host_mask),
                jnp.asarray(host_vals),
            )
        for dev, pairs in groups.values():
            sidx = np.full((eng.slots,), eng.slots, np.int32)  # OOB: dropped
            for slot, row in pairs:
                sidx[row] = slot
            self._tokens_dev = self._scatter_rows(
                self._tokens_dev, jnp.asarray(sidx), dev
            )
        for slot, dev in scalars:
            self._tokens_dev = self._scatter_one(
                self._tokens_dev, jnp.asarray(slot, jnp.int32), dev
            )

    def _splitkv_step(self):
        if self._astep_sk is None:
            eng = self.eng
            model, impl, quant_impl = eng.model, eng._impl, eng._quant_impl
            mesh, axis = eng.mesh, eng.splitkv_axis

            affine = getattr(eng, "page_affine", False)

            def serve_async_decode_step_splitkv(p, s, t):
                with catt.use_splitkv(mesh, axis, page_affine=affine):
                    logits, st = model.decode_step(
                        p, s, t, impl=impl, quant_impl=quant_impl
                    )
                nxt, finite = serve_select_tokens(logits)
                return nxt, finite, nxt[:, None], st

            self._astep_sk = jax.jit(serve_async_decode_step_splitkv,
                                     donate_argnums=(1, 2))
        return self._astep_sk

    # -------------------------------------------------- consumption side

    def _consume_one(self) -> None:
        """Consume the oldest in-flight step: one blocking device->host
        transfer (the async runtime's only sync, attributed to
        ``device_wait``), then the sync engine's own per-slot advance body
        against the dispatch-time snapshot.  Snapshot entries whose slot was
        retired or preempted since dispatch are discarded — their results
        belong to a request that already left."""
        eng = self.eng
        rec = self.inflight.popleft()
        with eng._phase("device_wait"):
            nxt = np.asarray(rec.nxt)
            finite = np.asarray(rec.finite)
            for slot, (dev, row, seq) in rec.lazy.items():
                req = eng.sched.active.get(slot)
                if req is not None and req.admit_seq == seq:
                    arr = np.asarray(dev)
                    eng.tokens[slot, 0] = (
                        int(arr[row]) if row is not None else int(arr)
                    )
                cur = self._lazy_first.get(slot)
                if cur is not None and cur[2] == seq:
                    self._lazy_first.pop(slot, None)
        if not self.inflight:
            self._idle_since = time.perf_counter()
        now = time.perf_counter()
        dt = now - rec.t0  # pipeline latency of this token
        with eng._phase("advance"):
            for slot, req, seq in rec.snap:
                cur = eng.sched.active.get(slot)
                if cur is not req or req.admit_seq != seq:
                    eng.metrics.inc("discarded_steps")
                    continue
                poisoned = (
                    eng.faults is not None
                    and eng.faults.fires(
                        "poison_logits", cycle=rec.cycle, uid=req.uid,
                        progress=len(req.out_tokens),
                    )
                )
                bad = None
                if eng.guard_logits and (poisoned or not bool(finite[slot])):
                    bad = "non-finite logits row"
                eng._advance_one(
                    slot, req, int(nxt[slot]), bad, dt, now, cycle=rec.cycle
                )
            eng.metrics.inc("steps")
        self.last_progress = now
        if (eng.paged and eng.audit_every
                and rec.cycle % eng.audit_every == 0):
            eng.audit().raise_if_violations()
