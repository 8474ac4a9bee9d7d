"""Persistent prefix-cache tier (ISSUE 10): the RETAINED page state.

Correctness contract pinned here:

* **survival**: a prefix-registered page whose last holder departs stays
  resident (RETAINED, index entry live) instead of returning to the free
  list, and a later admission over the same prompt hits it exactly like a
  live shared page — bitwise-identical tokens to the live-hit run, and
  retained page *contents* bitwise what a cold re-prefill would commit
  (docs/SERVING.md §9: sharing itself is not bitwise vs a raw-bf16 full
  prefill, so the oracle for a retained hit is the live hit);
* **reclaim ordering**: the retained tier is drained (LRU-first, prefix
  index invalidated atomically) before backpressure is declared or any
  victim is preempted — retention adds capacity, never steals it;
* **invisibility**: with no cross-request prompt reuse, retention changes
  no output under pressure, preemption, async runtime, or spec-decode;
* **auditability**: a seeded retained/index mismatch is detected;
* **fault discipline**: the seeded ``evict_storm`` fault force-reclaims
  retained pages deterministically and replays from its seed.
"""
import jax
import numpy as np
import pytest

from repro.configs.base import smoke_config
from repro.models.zoo import build_model
from repro.serve import (
    FaultPlan,
    PagePool,
    Request,
    ServeEngine,
    audit_engine,
)
from repro.serve.telemetry import MetricsRegistry

BLOCK = 32


# --------------------------------------------------------------------------
# PagePool units: the third state's accounting
# --------------------------------------------------------------------------

def _retaining_pool(n=10, scratch=2, **kw):
    pool = PagePool(n, n_scratch=scratch, **kw)
    pool.retainable = lambda page: True
    return pool


def test_free_moves_retainable_page_to_retained_tier():
    pool = _retaining_pool()
    released = []
    pool.on_release = released.append
    pool.reserve(2)
    a, b = pool.alloc(), pool.alloc()
    pool.free(a)
    pool.free(b)
    # retained, not free: still counted in n_used, on_release NOT fired
    assert pool.n_retained == 2 and pool.retained_pages() == [a, b]
    assert pool.is_retained(a) and pool.refcount(a) == 0
    assert pool.n_used == 2 and pool.committed == 2
    assert a not in pool.free_pages() and b not in pool.free_pages()
    assert released == []
    # non-retainable pages keep the old lifecycle
    pool.retainable = lambda page: False
    pool.reserve(1)
    c = pool.alloc()
    pool.free(c)
    assert not pool.is_retained(c) and c in pool.free_pages()
    assert released == [c]


def test_retain_promotes_retained_page_back_to_committed():
    pool = _retaining_pool()
    pool.reserve(1, owner="alice")
    a = pool.alloc(owner="alice")
    pool.free(a, owner="alice")
    assert pool.is_retained(a)
    used_before, committed_before = pool.n_used, pool.committed
    promoted = pool.retain(a, owner="bob")
    assert promoted is True  # the scheduler counts these as retained hits
    # budget-neutral: the page was already in n_used
    assert pool.n_used == used_before and pool.committed == committed_before
    assert not pool.is_retained(a)
    assert pool.refcount(a) == 1 and pool.holders(a) == ["bob"]
    # a plain share of a live page is not a promotion
    assert pool.retain(a, owner="carol") is False
    pool.free(a, owner="bob")
    pool.free(a, owner="carol")


def test_reserve_reclaims_lru_retained_before_backpressure():
    metrics = MetricsRegistry()
    pool = _retaining_pool(10, 2, metrics=metrics)  # capacity 8
    released = []
    pool.on_release = released.append
    pool.reserve(3)
    pages = [pool.alloc() for _ in range(3)]
    for p in pages:
        pool.free(p)
    assert pool.n_retained == 3
    # 8 capacity - 3 retained-in-use = 5 guaranteed; asking 7 must reclaim
    # exactly 2 pages, LRU-oldest first, firing on_release for each
    assert pool.reserve(7) is True
    assert pool.n_retained == 1 and pool.retained_pages() == [pages[2]]
    assert released == pages[:2]
    assert pool.reclaim_count == 2
    assert metrics.value("retained_reclaims") == 2
    # over-asking reclaims the rest, then still refuses honestly
    assert pool.reserve(5) is False
    assert pool.n_retained == 0 and released == pages
    assert pool.reserved == 7  # the failed reserve changed no accounting


def test_covered_alloc_reclaims_when_free_list_is_dry():
    pool = _retaining_pool(6, 2)  # capacity 4
    pool.reserve(4)
    pages = [pool.alloc() for _ in range(4)]
    for p in pages:
        pool.free(p)
    assert pool.n_free == 0 and pool.n_retained == 4
    # retained pages count as used, so this reserve must first reclaim
    assert pool.reserve(2)
    got = [pool.alloc(), pool.alloc()]
    assert set(got) == set(pages[:2])  # LRU order: oldest reclaimed first
    assert pool.n_retained == 2
    for p in got:
        pool.free(p)


def test_shard_pinned_alloc_and_shard_local_reclaim():
    pool = PagePool(12, n_scratch=2, shards=3)  # shards: [2,3],[4..7],[8..11]
    pool.retainable = lambda page: True
    assert pool.shard_of(5) == 1 and pool.shard_of(8) == 2
    pool.reserve(4)
    a = pool.alloc(shard=1)
    assert pool.shard_of(a) == 1
    # unpinned allocs round-robin across shards with free pages
    spread = {pool.shard_of(pool.alloc()) for _ in range(3)}
    assert spread == {0, 1, 2}
    # drain shard 1 then retain its last page: a pinned alloc must reclaim
    # in-shard even while other shards have free pages
    while pool.shard_free(1):
        pool.reserve(1)
        pool.alloc(shard=1)
    pool.free(a)
    assert pool.is_retained(a) and not pool.shard_free(1)
    assert pool.shard_available(1)  # reclaimable counts as available
    pool.reserve(1)
    again = pool.alloc(shard=1)
    assert again == a and pool.n_retained == 0
    # now shard 1 is truly dry: pinned alloc raises even with free elsewhere
    pool.reserve(1)
    with pytest.raises(RuntimeError, match="exhausted in shard 1"):
        pool.alloc(shard=1)
    assert not pool.shard_available(1)
    assert pool.n_free > 0  # the pool as a whole was not empty


def test_force_reclaim_is_lru_ordered_and_bounded():
    pool = _retaining_pool(8, 2)
    released = []
    pool.on_release = released.append
    pool.reserve(3)
    pages = [pool.alloc() for _ in range(3)]
    for p in pages:
        pool.free(p)
    assert pool.reclaim_retained(2) == 2
    assert released == pages[:2]  # oldest-departed first
    assert pool.reclaim_retained(99) == 1  # bounded by the tier
    assert pool.reclaim_retained(1) == 0  # empty tier is a safe no-op


def test_pool_gauges_follow_every_mutation():
    metrics = MetricsRegistry()
    pool = _retaining_pool(8, 2, metrics=metrics)
    pool.reserve(3)
    pages = [pool.alloc() for _ in range(2)]
    pool.free(pages[0])
    assert metrics.value("pool_pages_used") == 2
    assert metrics.value("pool_pages_retained") == 1
    assert metrics.value("pool_pages_reserved") == 1
    assert metrics.value("pool_pages_committed") == 3
    assert metrics.value("pool_occupancy") == pytest.approx(2 / 6)
    # a gauge whose value did not change is skipped, not left stale
    assert pool.reclaim_retained(1) == 1
    assert metrics.value("pool_pages_used") == 1
    assert metrics.value("pool_pages_retained") == 0
    assert metrics.value("pool_pages_reserved") == 1
    assert metrics.value("pool_pages_committed") == 2


# --------------------------------------------------------------------------
# Engine integration
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    cfg = smoke_config("llama3-8b").with_(kv_bits=4, kv_block=BLOCK)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompt(cfg, rng, n):
    return rng.integers(0, cfg.vocab, n).astype(np.int32)


def test_holder_departure_survival_and_bitwise_readmission(small_model):
    """Tentpole acceptance: A's prefix pages survive A's departure; B's
    re-admission hits them and produces bitwise the tokens of a *live* hit
    (same share structure, donor still resident), and the retained pages'
    contents are bitwise what B's own cold prefill would have committed."""
    cfg, model, params = small_model
    rng = np.random.default_rng(5)
    pa = _prompt(cfg, rng, 3 * BLOCK)

    # oracle 1: live hit — A still resident when B admits
    live = ServeEngine(model, params, slots=2, max_seq=256)
    la = Request(uid=0, prompt=pa.copy(), max_new_tokens=40)
    lb = Request(uid=1, prompt=pa.copy(), max_new_tokens=4)
    live.submit(la)
    live.step()
    live.submit(lb)
    live.run()

    # oracle 2: cold re-prefill — retention off, A fully departed
    cold = ServeEngine(model, params, slots=2, max_seq=256)
    ca = Request(uid=0, prompt=pa.copy(), max_new_tokens=4)
    cold.submit(ca)
    cold.run()
    assert cold.pool.n_retained == 0 and len(cold.sched.index) == 0

    # retention on: A departs, pages move to RETAINED, index stays live
    engine = ServeEngine(model, params, slots=2, max_seq=256,
                         retain_prefix=True)
    a = Request(uid=0, prompt=pa.copy(), max_new_tokens=4)
    engine.submit(a)
    engine.run()
    assert a.done and a.out_tokens == ca.out_tokens
    assert engine.pool.n_retained == 3  # all three full prompt blocks
    assert len(engine.sched.index) == 3
    retained = engine.pool.retained_pages()
    assert all(engine.pool.refcount(p) == 0 for p in retained)
    # retained page contents == the cold engine's committed pages, bitwise
    for blk, (rp, cp) in enumerate(zip(a.pages[:3], ca.pages[:3])):
        ours = np.asarray(engine.state["caches"][0].kw[:, rp])
        theirs = np.asarray(cold.state["caches"][0].kw[:, cp])
        np.testing.assert_array_equal(ours, theirs, err_msg=f"block {blk}")

    prefilled_before = engine.stats["prefill_tokens"]
    b = Request(uid=1, prompt=pa.copy(), max_new_tokens=4)
    engine.submit(b)
    engine.run()
    assert b.done
    # the hit promoted retained pages (capped at one-suffix-token rule)
    assert b.shared_pages == a.pages[:2]
    assert engine.sched.stats["prefix_retained_hits"] == 2
    assert engine.stats["prefill_tokens"] - prefilled_before == BLOCK
    assert engine.stats["prefill_tokens_saved"] == 2 * BLOCK
    # bitwise the live-hit tokens — retention is invisible to the sharer
    assert b.out_tokens == lb.out_tokens
    assert audit_engine(engine).ok
    assert engine.summary()["prefix_hit_rate"] > 0


def _workload(cfg, n=5):
    """Distinct multi-block prompts (no cross-request sharing), decode
    spanning block boundaries — the pressure harness's canonical shape."""
    rng = np.random.default_rng(42)
    return [
        Request(
            uid=i,
            prompt=rng.integers(
                0, cfg.vocab, int(rng.integers(34, 48))).astype(np.int32),
            max_new_tokens=int(rng.integers(24, 32)),
        )
        for i in range(n)
    ]


@pytest.fixture(scope="module")
def baseline_outputs(small_model):
    cfg, model, params = small_model
    engine = ServeEngine(model, params, slots=2, max_seq=128)
    reqs = _workload(cfg)
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done for r in reqs)
    return {r.uid: list(r.out_tokens) for r in reqs}


def test_reclaim_drains_retained_before_preemption(small_model,
                                                   baseline_outputs):
    """With the pool oversubscribed and every completed prompt leaving
    retained pages behind, admission/extension pressure is served by
    reclaiming the tier — outputs stay bitwise the unpressured run, and a
    retention run never preempts more than the retention-free run."""
    cfg, model, params = small_model

    def run(**kw):
        engine = ServeEngine(model, params, slots=2, max_seq=128,
                             n_pages=2 + 4, reserve_policy="expected",
                             expected_quantile=0.0, audit_every=1, **kw)
        reqs = _workload(cfg)
        for r in reqs:
            engine.submit(r)
        engine.run()
        return engine, reqs

    eng_off, _ = run()
    engine, reqs = run(retain_prefix=True)
    assert all(r.done for r in reqs), [r.phase for r in reqs]
    for r in reqs:
        assert r.out_tokens == baseline_outputs[r.uid]
    # pressure was real and the tier absorbed it
    assert engine.pool.reclaim_count > 0
    assert engine.stats["retained_reclaims"] == engine.pool.reclaim_count
    assert engine.stats["preempted"] <= eng_off.stats["preempted"]
    # drain leaves the survivors retained but accounted: every page is
    # free or retained, nothing leaked, nothing reserved
    assert engine.pool.reserved == 0
    assert engine.pool.n_free + engine.pool.n_retained == engine.pool.capacity
    assert audit_engine(engine).ok


@pytest.mark.parametrize("mode", ["async", "spec", "pressure"])
def test_retention_invisible_across_runtime_matrix(small_model,
                                                   baseline_outputs, mode):
    """No cross-request sharing -> retention must change no output, under
    the async runtime, spec-decode, and pool pressure alike."""
    cfg, model, params = small_model
    kw = {
        "async": dict(async_runtime=True),
        "spec": dict(spec_k=3),
        "pressure": dict(n_pages=2 + 4, reserve_policy="expected",
                         expected_quantile=0.0),
    }[mode]
    engine = ServeEngine(model, params, slots=2, max_seq=128,
                         retain_prefix=True, **kw)
    reqs = _workload(cfg)
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done for r in reqs), [r.phase for r in reqs]
    for r in reqs:
        assert r.out_tokens == baseline_outputs[r.uid], mode
    assert engine.pool.n_retained > 0  # the tier was actually populated
    assert audit_engine(engine).ok


def test_retained_readmission_identical_across_runtimes(small_model):
    """The retained-hit path itself is runtime-invariant: sync, async and
    spec-decode re-admissions over a retained prefix emit identical
    streams."""
    cfg, model, params = small_model
    rng = np.random.default_rng(11)
    pa = _prompt(cfg, rng, 3 * BLOCK)

    def run(**kw):
        engine = ServeEngine(model, params, slots=2, max_seq=256,
                             retain_prefix=True, **kw)
        a = Request(uid=0, prompt=pa.copy(), max_new_tokens=4)
        engine.submit(a)
        engine.run()
        b = Request(uid=1, prompt=pa.copy(), max_new_tokens=6)
        engine.submit(b)
        engine.run()
        assert engine.sched.stats["prefix_retained_hits"] > 0
        return list(b.out_tokens)

    sync = run()
    assert run(async_runtime=True) == sync
    assert run(spec_k=3) == sync


def test_auditor_detects_retained_index_mismatch(small_model):
    cfg, model, params = small_model
    engine = ServeEngine(model, params, slots=2, max_seq=256,
                         retain_prefix=True)
    rng = np.random.default_rng(12)
    a = Request(uid=0, prompt=_prompt(cfg, rng, 2 * BLOCK), max_new_tokens=4)
    engine.submit(a)
    engine.run()
    assert engine.pool.n_retained == 2
    assert audit_engine(engine).ok
    # seed breach 1: index forgets a page the pool still retains
    page = engine.pool.retained_pages()[0]
    engine.sched.index.forget_page(page)
    report = audit_engine(engine)
    assert not report.ok
    assert any("not registered" in v for v in report.violations)
    # seed breach 2: the same page also appears on a free list
    engine.sched.index.register(
        engine.sched.index.chain(a.prompt)[:1], [page], a.prompt
    )
    engine.pool._shard_free[0].append(page)
    report = audit_engine(engine)
    assert not report.ok
    assert any("free" in v and "retained" in v for v in report.violations)


def test_evict_storm_fault_is_deterministic_and_survivable(small_model):
    """The seeded evict_storm force-reclaims retained pages mid-run: the
    victims' index entries invalidate atomically, later admissions just
    re-prefill cold, outputs for untouched requests are unchanged, and the
    whole scenario replays bitwise from its seed."""
    cfg, model, params = small_model
    rng = np.random.default_rng(13)
    pa = _prompt(cfg, rng, 3 * BLOCK)

    def run():
        # fire every cycle: the firing after A's departure (the storm
        # consult precedes admission within a cycle) prunes the tier
        # before B's lookup can hit it
        plan = FaultPlan(seed=3, fire_at={"evict_storm": tuple(range(32))},
                         storm_pages=2)
        engine = ServeEngine(model, params, slots=2, max_seq=256,
                             retain_prefix=True, faults=plan, audit_every=1)
        a = Request(uid=0, prompt=pa.copy(), max_new_tokens=4)
        engine.submit(a)
        engine.run()  # A departs -> 3 retained
        retained_after_a = engine.pool.n_retained
        b = Request(uid=1, prompt=pa.copy(), max_new_tokens=4)
        engine.submit(b)
        engine.run()
        return engine, plan, a, b, retained_after_a

    engine, plan, a, b, retained_after_a = run()
    assert retained_after_a == 3
    assert plan.fired("evict_storm") >= 1
    assert engine.pool.reclaim_count >= 2
    # the storm emptied the leading chain before B admitted: B re-prefilled
    # cold instead of hitting the pruned tier
    assert engine.sched.stats["prefix_retained_hits"] == 0
    assert engine.stats["faults_injected"] >= 1
    assert a.done and b.done
    assert audit_engine(engine).ok
    engine2, plan2, a2, b2, _ = run()
    assert plan2.log == plan.log
    assert a2.out_tokens == a.out_tokens and b2.out_tokens == b.out_tokens


def test_evict_storm_with_empty_tier_is_noop(small_model):
    cfg, model, params = small_model
    plan = FaultPlan(seed=4, evict_storm=1.0, storm_pages=4)
    engine = ServeEngine(model, params, slots=2, max_seq=128, faults=plan)
    reqs = _workload(cfg, n=2)
    for r in reqs:
        engine.submit(r)
    engine.run()  # retention off: the tier is always empty
    assert all(r.done for r in reqs)
    assert plan.fired("evict_storm") > 0
    assert engine.pool.reclaim_count == 0
    assert audit_engine(engine).ok


def test_retain_prefix_off_is_bitwise_seed_behavior(small_model):
    """Default-off: without retain_prefix the pool never retains and drain
    invariants stay exactly the pre-tier contract."""
    cfg, model, params = small_model
    engine = ServeEngine(model, params, slots=2, max_seq=256)
    rng = np.random.default_rng(14)
    a = Request(uid=0, prompt=_prompt(cfg, rng, 2 * BLOCK), max_new_tokens=4)
    engine.submit(a)
    engine.run()
    assert engine.pool.n_retained == 0
    assert engine.pool.n_free == engine.pool.capacity
    assert len(engine.sched.index) == 0
