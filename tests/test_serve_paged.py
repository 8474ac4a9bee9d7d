"""Paged serving subsystem: page-pool allocator, scheduler lifecycle,
bucketed prefill compile behaviour, backpressure/reclaim, and full-engine
paged-vs-dense parity (the PR's acceptance criterion)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import smoke_config
from repro.core import qcache
from repro.models.zoo import build_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.pages import PagePool
from repro.serve.scheduler import Phase, Scheduler, bucket_for


# --------------------------------------------------------------------------
# PagePool unit behaviour
# --------------------------------------------------------------------------

def test_pagepool_freelist_and_refcounts():
    pool = PagePool(8, n_scratch=2)
    assert pool.capacity == 6 and pool.n_free == 6
    assert pool.reserve(6)
    assert not pool.reserve(1)  # full commitment -> backpressure
    a, b = pool.alloc(), pool.alloc()
    assert a >= 2 and b >= 2 and a != b  # scratch pages never allocated
    assert pool.n_used == 2
    # allocs converted two reserved units into allocated ones; the
    # commitment total is unchanged (shared budget, counted once)
    assert pool.reserved == 4 and pool.committed == 6
    assert not pool.reserve(1)
    pool.retain(a)
    assert pool.refcount(a) == 2
    pool.free(a)
    assert pool.n_used == 2  # refcount 1 left -> not yet returned
    pool.free(a)
    pool.free(b)
    assert pool.n_free == 6
    pool.release(4)  # the never-allocated remainder
    assert pool.reserved == 0
    assert pool.reserve(1)
    with pytest.raises(ValueError):
        pool.free(b)  # double free


def test_pagepool_alloc_without_reservation_guard():
    pool = PagePool(3, n_scratch=1)
    with pytest.raises(RuntimeError):
        pool.alloc()  # covered alloc with no reservation outstanding
    pool.alloc(covered=False)
    pool.alloc(covered=False)
    with pytest.raises(RuntimeError):
        pool.alloc(covered=False)  # exhausted: would over-commit


# --------------------------------------------------------------------------
# Scheduler: admission order, bucketing, backpressure
# --------------------------------------------------------------------------

def _req(uid, plen, max_new=4):
    return Request(uid=uid, prompt=np.zeros(plen, np.int32), max_new_tokens=max_new)


def test_bucket_for_powers_of_two():
    assert bucket_for(1) == 16
    assert bucket_for(16) == 16
    assert bucket_for(17) == 32
    assert bucket_for(100) == 128


def test_admission_fifo_order_and_grouping():
    pool = PagePool(32, n_scratch=4)
    sched = Scheduler(slots=4, pool=pool, block_n=32, max_seq=256)
    for i, plen in enumerate([5, 20, 7, 40, 9]):  # buckets 16,32,16,64,16
        sched.submit(_req(i, plen))
    groups = sched.admit()  # 4 slots -> first four admitted, FIFO
    admitted = [r.uid for g in groups.values() for r in g]
    assert sorted(admitted) == [0, 1, 2, 3]
    # slots assigned in submission order
    assert [sched.active[s].uid for s in sorted(sched.active)] == [0, 1, 2, 3]
    assert [r.uid for r in groups[16]] == [0, 2]
    assert [r.uid for r in groups[32]] == [1]
    assert [r.uid for r in groups[64]] == [3]
    assert all(r.phase == Phase.PREFILL for g in groups.values() for r in g)
    # uid 4 waits for a slot; completing uid 0 frees one
    sched.complete(sched.active[0])
    (g,) = sched.admit().values()
    assert [r.uid for r in g] == [4]


def test_admission_backpressure_is_strict_fifo():
    pool = PagePool(8, n_scratch=2)  # capacity 6
    sched = Scheduler(slots=4, pool=pool, block_n=32, max_seq=1024)
    big = _req(0, 150, max_new=50)  # needs (150+50)//32 = 6 pages
    small = _req(1, 5, max_new=4)   # needs 0 pages
    pool.reserve(1)  # someone already holds a page
    sched.submit(big)
    sched.submit(small)
    groups = sched.admit()
    # head can't reserve -> nothing admitted, nothing overtakes it
    assert groups == {}
    assert sched.stats["backpressure_events"] == 1
    pool.release(1)
    groups = sched.admit()
    admitted = [r.uid for g in groups.values() for r in g]
    assert admitted == [0, 1]
    assert pool.reserved == 6


# --------------------------------------------------------------------------
# Engine: bucketed prefill compiles, backpressure/reclaim, parity
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    cfg = smoke_config("llama3-8b").with_(kv_bits=4, kv_block=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_prefill_bucketing_one_compile_per_bucket(small_model):
    cfg, model, params = small_model
    engine = ServeEngine(model, params, slots=4, max_seq=128, min_bucket=16)
    assert engine.paged
    rng = np.random.default_rng(0)

    def sub(plen, uid):
        engine.submit(Request(
            uid=uid, prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
            max_new_tokens=2))

    sub(5, 0)   # bucket 16
    sub(9, 1)   # bucket 16 (same cycle, same call)
    sub(20, 2)  # bucket 32
    engine.step()
    assert engine.stats["prefill_calls"] == 2  # one per bucket this cycle
    assert engine._prefill._cache_size() == 2
    sub(11, 3)  # bucket 16 again, later cycle: new call, NO new compile
    engine.run()
    assert engine.stats["prefill_calls"] == 3
    assert engine._prefill._cache_size() == 2  # jit cache keyed on bucket


def test_page_exhaustion_backpressure_and_reclaim(small_model):
    cfg, model, params = small_model
    # capacity 2 pages: each request needs (30+6)//32 = 1 page -> two in
    # flight, the third waits for a completion to return pages
    engine = ServeEngine(model, params, slots=3, max_seq=64,
                         n_pages=3 + 2)
    assert engine.pool.capacity == 2
    rng = np.random.default_rng(1)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 30).astype(np.int32),
                    max_new_tokens=6) for i in range(3)]
    for r in reqs:
        engine.submit(r)
    engine.step()
    assert len(engine.sched.active) == 2  # third hit backpressure
    assert engine.sched.stats["backpressure_events"] >= 1
    stats = engine.run()
    assert all(r.done for r in reqs)
    assert all(len(r.out_tokens) == 6 for r in reqs)
    # pages reclaimed, reservations returned
    assert engine.pool.n_free == engine.pool.capacity
    assert engine.pool.reserved == 0
    assert stats["sched_completed"] == 3


def test_preempt_free_steady_state(small_model):
    """Admission reservations guarantee decode-time page allocation never
    fails: a saturating mixed workload completes with every allocation
    served from the free list (alloc raises if the invariant breaks)."""
    cfg, model, params = small_model
    engine = ServeEngine(model, params, slots=2, max_seq=128,
                         n_pages=2 + 4)
    rng = np.random.default_rng(2)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, int(rng.integers(4, 60))).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 10)))
            for i in range(6)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(r.done for r in reqs)
    assert engine.pool.n_free == engine.pool.capacity


def test_paged_engine_matches_dense_oracle(small_model):
    """Acceptance criterion: a mixed workload (short + multi-block prompts,
    staggered arrivals) through the paged engine produces per-token outputs
    identical to a dense-cache single-request oracle."""
    cfg, model, params = small_model
    rng = np.random.default_rng(3)
    specs = [(30, 6), (7, 5), (44, 4)]  # (prompt_len, max_new); 30+6 crosses
    prompts = [rng.integers(0, cfg.vocab, l).astype(np.int32) for l, _ in specs]

    def oracle(prompt, max_new):
        logits, st = model.prefill(params, {"tokens": jnp.asarray(prompt[None])}, 128)
        tok = int(np.argmax(np.asarray(logits)[0, -1]))
        step = jax.jit(functools.partial(model.decode_step, impl="auto",
                                         quant_impl="auto"))
        out = []
        for _ in range(max_new):
            out.append(tok)
            logits, st = step(params, st, jnp.asarray([[tok]], jnp.int32))
            tok = int(np.argmax(np.asarray(logits)[0, 0]))
        return out

    want = [oracle(p, mn) for p, (_, mn) in zip(prompts, specs)]

    engine = ServeEngine(model, params, slots=2, max_seq=128)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=mn)
            for i, (p, (_, mn)) in enumerate(zip(prompts, specs))]
    engine.submit(reqs[0])  # staggered arrivals
    engine.step()
    engine.submit(reqs[1])
    engine.step()
    engine.submit(reqs[2])
    engine.run()
    for i, (r, w) in enumerate(zip(reqs, want)):
        assert r.done
        assert r.out_tokens == w, f"request {i} diverged from dense oracle"


# --------------------------------------------------------------------------
# Paged append: gated fused flush (jaxpr proof) + cache math
# --------------------------------------------------------------------------

def _collect_prims(jaxpr, into):
    from jax.extend import core as jc

    for e in jaxpr.eqns:
        into.add(e.primitive.name)
        for val in e.params.values():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for w in vals:
                if isinstance(w, jc.ClosedJaxpr):
                    _collect_prims(w.jaxpr, into)
    return into


@pytest.mark.parametrize("quant_impl", ["xla", "pallas"])
def test_paged_hot_path_does_no_quant_work(quant_impl):
    """The acceptance criterion's jaxpr proof, paged edition: quantize/pack
    work lives exclusively inside the flush branch of a single `cond`; the
    per-token paged append traced at the top level carries none of it."""
    pc = qcache.init_paged_cache(12, 2, 2, 128, 4, bits=4, block_n=128)
    k = jnp.ones((2, 2, 1, 128), jnp.bfloat16)
    v = jnp.ones((2, 2, 1, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        functools.partial(qcache.paged_append_decode, quant_impl=quant_impl)
    )(pc, k, v)
    quant_marker = "pallas_call" if quant_impl == "pallas" else "shift_left"
    top = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert "cond" in top
    assert quant_marker not in top and "round" not in top
    (cond_eqn,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]
    branch_has_quant = [
        quant_marker in _collect_prims(br.jaxpr, set())
        for br in cond_eqn.params["branches"]
    ]
    assert sum(branch_has_quant) == 1, branch_has_quant


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_flush_commits_through_table(impl):
    """Filling slot 1's residual commits its quantized block into the pool
    page its table points at; other pool pages (incl. scratch) are unchanged;
    the dense flush of the same content produces bitwise-identical packing."""
    import dataclasses

    from repro.kernels.kv_quant import ref as kq_ref

    B, H, D, BLOCK = 3, 2, 128, 128
    pc = qcache.init_paged_cache(12, B, H, D, 4, bits=4, block_n=BLOCK)
    table = np.asarray(pc.page_table).copy()
    table[1, 0] = 7
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    k = jax.random.normal(ks[0], (B, H, BLOCK, D)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[1], (B, H, BLOCK, D)).astype(jnp.bfloat16)
    pc = dataclasses.replace(
        pc, page_table=jnp.asarray(table),
        k_res=pc.k_res.at[1, :, : BLOCK - 1].set(k[1, :, : BLOCK - 1]),
        v_res=pc.v_res.at[1, :, : BLOCK - 1].set(v[1, :, : BLOCK - 1]),
        res_len=jnp.asarray([3, BLOCK - 1, 0], jnp.int32),
    )
    pc2 = qcache.paged_append_decode(
        pc, k[:, :, BLOCK - 1 : BLOCK], v[:, :, BLOCK - 1 : BLOCK],
        quant_impl=impl,
    )
    assert int(pc2.pack_blocks[1]) == 1 and int(pc2.res_len[1]) == 0
    assert int(pc2.res_len[0]) == 4 and int(pc2.res_len[2]) == 1
    # page 7 now holds the quantized block; parity vs direct quantization
    kw_want, ks_want, kz_want = kq_ref.quantize_kv_ref(
        np.asarray(pc2.k_res[1])[None], 4, "channel", block_n=BLOCK
    )
    np.testing.assert_array_equal(np.asarray(pc2.kw[7]), np.asarray(kw_want)[0, :, 0])
    np.testing.assert_array_equal(
        np.asarray(pc2.k_scale[7]), np.asarray(ks_want)[0, :, 0])
    # untouched pages stay zero (e.g. page 8 and slot 0's scratch page 0)
    assert not np.asarray(pc2.kw[8]).any()
    assert not np.asarray(pc2.kw[3]).any()


def test_ragged_prefill_matches_exact(small_model):
    """Bucket-padded ragged prefill: occupancy + residual + logits equal the
    exact-length prefill per sequence."""
    cfg, model, params = small_model
    rng = np.random.default_rng(4)
    L = 64
    lens = [64, 37, 10]
    toks = np.zeros((3, L), np.int32)
    prompts = []
    for i, l in enumerate(lens):
        p = rng.integers(0, cfg.vocab, l).astype(np.int32)
        prompts.append(p)
        toks[i, :l] = p
    logits_r, st_r = model.prefill(
        params, {"tokens": jnp.asarray(toks)}, L,
        lengths=jnp.asarray(lens, jnp.int32),
    )
    for i, (p, l) in enumerate(zip(prompts, lens)):
        lg, st = model.prefill(params, {"tokens": jnp.asarray(p[None])}, L)
        np.testing.assert_allclose(
            np.asarray(logits_r)[i, 0], np.asarray(lg)[0, 0],
            rtol=2e-3, atol=2e-3)
        c_r, c_1 = st_r["caches"][0], st["caches"][0]
        assert int(c_r.pack_blocks[0, i]) == int(c_1.pack_blocks[0, 0]) == l // cfg.kv_block
        rl = l % cfg.kv_block
        assert int(c_r.res_len[0, i]) == rl
        if rl:
            np.testing.assert_allclose(
                np.asarray(c_r.k_res)[:, i, :, :rl],
                np.asarray(c_1.k_res)[:, 0, :, :rl], rtol=2e-2, atol=2e-2)
        # valid packed blocks are bitwise identical (per-block quantization)
        nblk = l // cfg.kv_block
        if nblk:
            np.testing.assert_array_equal(
                np.asarray(c_r.kw)[:, i, :, :nblk],
                np.asarray(c_1.kw)[:, 0, :, :nblk])
        assert int(st_r["pos"][i]) == l


def test_mesh_aligned_init_cache_block_align():
    c = qcache.init_cache(1, 2, 64, 5 * 128, block_align=4)
    assert c.kw.shape[2] % 4 == 0
    c2 = qcache.init_cache(1, 2, 64, 5 * 128)
    assert c2.kw.shape[2] == 5


def test_mla_serves_paged_by_default():
    """MLA's latent cache now pages (shared_kv pools) — no dense fork."""
    cfg = smoke_config("deepseek-v3-671b").with_(kv_bits=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, slots=2, max_seq=64)
    assert engine.paged and engine.spec.shared_kv
    assert engine.state["caches"][0].vw is None  # no V-side pools at all
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 6).astype(np.int32),
                    max_new_tokens=3) for i in range(3)]
    for r in reqs:
        engine.submit(r)
    stats = engine.run()
    assert all(r.done for r in reqs)
    assert stats["decoded_tokens"] == 9


def test_nokv_shim_engine_serves_and_accounts():
    """xLSTM (no KV anywhere) serves through the exact-length shim: same
    scheduler, same decode cycle, per-token accounting intact (pos advances
    with every decoded token; budget retirement counted exactly once)."""
    cfg = smoke_config("xlstm-1.3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, slots=2, max_seq=64)
    assert not engine.paged and engine.pool is None
    rng = np.random.default_rng(6)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 7).astype(np.int32),
                    max_new_tokens=3) for i in range(3)]
    for r in reqs:
        engine.submit(r)
    stats = engine.run()
    assert all(r.done for r in reqs)
    assert all(r.pos == 7 + 3 for r in reqs)  # the dense-shim drift fix
    assert stats["decoded_tokens"] == 9
    assert stats["budget_retired"] == 3  # counted exactly once each


def test_forced_shim_matches_paged_outputs():
    """`paged=False` forces the exact-length shim for a paged-capable model;
    outputs stay bitwise identical to the paged engine."""
    cfg = smoke_config("llama3-8b").with_(kv_bits=4, kv_block=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in (9, 40)]

    def run(paged):
        engine = ServeEngine(model, params, slots=2, max_seq=128, paged=paged)
        reqs = [Request(uid=i, prompt=p.copy(), max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            engine.submit(r)
        engine.run()
        return [r.out_tokens for r in reqs], engine

    want, shim = run(False)
    assert not shim.paged
    got, paged_eng = run(None)
    assert paged_eng.paged
    assert got == want
