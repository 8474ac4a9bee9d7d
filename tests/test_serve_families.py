"""Unified paged decode across cache families (the PR's acceptance
criteria): MLA latent paging and HybridLM mixed per-layer states decode
through kernels/paged_bitdecode bitwise-identically to their dense-slot
oracles, prefix sharing + COW work on the latent pools, and a jaxpr taint
proof shows hybrid SSM layers carry no page-table work."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import smoke_config
from repro.core import qcache
from repro.models.zoo import build_model
from repro.serve.engine import Request, ServeEngine

BLOCK = 32


def _model(arch):
    cfg = smoke_config(arch).with_(kv_bits=4, kv_block=BLOCK)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def mla_model():
    return _model("deepseek-v3-671b")


@pytest.fixture(scope="module")
def hybrid_model():
    return _model("zamba2-7b")


def _oracle(model, params, prompt, max_new, max_seq=128):
    """Dense-slot reference: exact-length prefill, its caches handed to the
    identity page table (``qcache.to_pages``), jitted decode loop."""
    logits, st = model.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                               max_seq)
    st = qcache.to_pages(st)
    tok = int(np.argmax(np.asarray(logits)[0, -1]))
    step = jax.jit(functools.partial(model.decode_step, impl="auto",
                                     quant_impl="auto"))
    out = []
    for _ in range(max_new):
        out.append(tok)
        logits, st = step(params, st, jnp.asarray([[tok]], jnp.int32))
        tok = int(np.argmax(np.asarray(logits)[0, 0]))
    return out


def _engine_vs_oracle(cfg, model, params):
    """Mixed workload (short + block-crossing prompts, staggered arrivals)
    through the paged engine vs the dense oracle, bitwise."""
    rng = np.random.default_rng(3)
    specs = [(30, 6), (7, 5), (44, 4)]  # 30+6 and 44 cross block boundaries
    prompts = [rng.integers(0, cfg.vocab, l).astype(np.int32) for l, _ in specs]
    want = [_oracle(model, params, p, mn) for p, (_, mn) in zip(prompts, specs)]

    engine = ServeEngine(model, params, slots=2, max_seq=128)
    assert engine.paged
    reqs = [Request(uid=i, prompt=p, max_new_tokens=mn)
            for i, (p, (_, mn)) in enumerate(zip(prompts, specs))]
    engine.submit(reqs[0])
    engine.step()
    engine.submit(reqs[1])
    engine.step()
    engine.submit(reqs[2])
    engine.run()
    for i, (r, w) in enumerate(zip(reqs, want)):
        assert r.done
        assert r.out_tokens == w, f"request {i} diverged from dense oracle"
    assert engine.pool.n_free == engine.pool.capacity
    assert engine.pool.reserved == 0


def test_mla_paged_engine_matches_dense_oracle(mla_model):
    """Acceptance criterion: MLA requests decode through the shared_kv
    latent page pools, bitwise-identical to the dense-slot outputs —
    prefix sharing and COW enabled (engine defaults)."""
    cfg, model, params = mla_model
    _engine_vs_oracle(cfg, model, params)


def test_hybrid_paged_engine_matches_dense_oracle(hybrid_model):
    """Acceptance criterion: HybridLM's attention caches page; its SSM
    side-state splices per slot; outputs bitwise match the dense oracle."""
    cfg, model, params = hybrid_model
    _engine_vs_oracle(cfg, model, params)


def test_hybrid_exact_prefill_grouping(hybrid_model):
    """Recurrent side-state tolerates no right-padding: admission groups
    are exact suffix lengths, and same-length prompts still batch into one
    prefill call."""
    cfg, model, params = hybrid_model
    engine = ServeEngine(model, params, slots=4, max_seq=128)
    assert engine.spec.exact_prefill and engine.sched.exact_buckets
    rng = np.random.default_rng(4)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
                    max_new_tokens=2)
            for i, plen in enumerate([9, 9, 20])]
    for r in reqs:
        engine.submit(r)
    engine.step()
    # two groups: the two 9-token prompts batch, the 20-token one is alone
    assert engine.stats["prefill_calls"] == 2
    engine.run()
    assert all(r.done for r in reqs)


def test_unserveable_family_refused_at_construction():
    """paged_spec() is None (enc-dec: prefill needs frame embeddings the
    Request cannot carry) -> the engine refuses at __init__ — not with an
    obscure error mid-prefill."""
    cfg = smoke_config("seamless-m4t-medium")
    model = build_model(cfg)
    assert model.paged_spec() is None
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="serveable cache family"):
        ServeEngine(model, params, slots=2, max_seq=64)


# --------------------------------------------------------------------------
# MLA prefix sharing + COW on the latent pools
# --------------------------------------------------------------------------

def test_mla_prefix_sharing_suffix_prefill(mla_model):
    """A sharer of a resident latent-chain prefix holds the donor's pages
    (refcounted) and prefills only its divergent suffix — the suffix attends
    the dequantized latent prior through each layer's up-projections."""
    cfg, model, params = mla_model
    engine = ServeEngine(model, params, slots=2, max_seq=256)
    rng = np.random.default_rng(5)
    pa = rng.integers(0, cfg.vocab, 3 * BLOCK).astype(np.int32)
    pb = np.concatenate([pa[: 2 * BLOCK],
                         rng.integers(0, cfg.vocab, 16).astype(np.int32)])
    a = Request(uid=0, prompt=pa, max_new_tokens=4)
    b = Request(uid=1, prompt=pb, max_new_tokens=4)
    engine.submit(a)
    engine.step()
    tokens_after_a = engine.stats["prefill_tokens"]
    engine.submit(b)
    engine.step()
    assert b.shared_pages == a.pages[:2]
    assert all(engine.pool.refcount(p) == 2 for p in b.shared_pages)
    assert engine.stats["prefill_tokens"] - tokens_after_a == 16
    assert engine.stats["prefill_tokens_saved"] == 2 * BLOCK
    engine.run()
    assert a.done and b.done
    assert engine.pool.n_free == engine.pool.capacity
    assert engine.summary()["prefix_hit_rate"] > 0


def test_mla_sharing_donor_bitwise_and_cow(mla_model):
    """Sharing never perturbs the donor (bitwise vs solo), and a spec-tail
    sharer copy-on-writes its first divergent flush on the latent pools —
    nothing shared is ever read, so the sharer is bitwise too."""
    cfg, model, params = mla_model

    def solo(prompt, max_new):
        eng = ServeEngine(model, params, slots=2, max_seq=256,
                          share_prefix=False)
        r = Request(uid=0, prompt=prompt, max_new_tokens=max_new)
        eng.submit(r)
        eng.run()
        return r.out_tokens

    rng = np.random.default_rng(7)
    pa = rng.integers(0, cfg.vocab, BLOCK + 8).astype(np.int32)
    pb = pa[:8].copy()  # strict mid-block prefix -> speculative tail

    engine = ServeEngine(model, params, slots=2, max_seq=256)
    a = Request(uid=0, prompt=pa, max_new_tokens=2 * BLOCK)
    b = Request(uid=1, prompt=pb, max_new_tokens=BLOCK)
    engine.submit(a)
    engine.step()
    page_a = a.pages[0]
    engine.submit(b)
    engine.step()
    assert b.spec_page == page_a
    assert engine.pool.refcount(page_a) == 2
    engine.run()
    assert engine.stats["cow_copies"] == 1
    assert b.out_tokens == solo(pb, BLOCK)
    assert a.out_tokens == solo(pa, 2 * BLOCK)
    assert engine.pool.n_free == engine.pool.capacity


# --------------------------------------------------------------------------
# jaxpr proof: hybrid SSM layers carry no page-table work
# --------------------------------------------------------------------------

def _propagate(jaxpr, tainted):
    """Forward taint within one (sub)jaxpr: returns (tainted set including
    derived vars, [scan eqns whose inputs are tainted]).

    Scans do NOT forward taint to their outputs: the question is which scans
    *receive the table* (page-table work), not which values are downstream
    of attention results (ordinary data flow — the tail Mamba scan of course
    consumes attention activations)."""
    tainted = set(tainted)
    tainted_scans = []
    for eqn in jaxpr.eqns:
        hit = any((not isinstance(v, jax.extend.core.Literal)) and v in tainted
                  for v in eqn.invars)
        if eqn.primitive.name == "scan":
            if hit:
                tainted_scans.append(eqn)
            continue
        if hit:
            tainted.update(eqn.outvars)
    return tainted, tainted_scans


def test_hybrid_ssm_layers_carry_no_page_table_work(hybrid_model):
    """Trace the hybrid paged decode step as a function of the page table
    and follow the table's taint through the jaxpr:

    * at top level, exactly ONE scan consumes table-derived values — the
      super-block scan that owns the shared-attention invocations; the tail
      Mamba scan never sees the table;
    * inside that scan's body, the inner Mamba-group scan does not consume
      table-derived values either.

    Together: paging work attaches only to the attention layers; the SSM
    recurrent updates carry zero page-table work.
    """
    cfg, model, params = hybrid_model
    assert model.tail, "smoke config should have a tail mamba stack"
    state = model.init_paged_decode_state(2, n_pages=8, nb_max=2)
    tokens = jnp.zeros((2, 1), jnp.int32)

    def f(table):
        caches = [dataclasses.replace(state["caches"][0], page_table=table)]
        st = dict(state, caches=caches)
        return model.decode_step(params, st, tokens)

    jaxpr = jax.make_jaxpr(f)(state["caches"][0].page_table).jaxpr
    (table_var,) = jaxpr.invars

    tainted, tainted_scans = _propagate(jaxpr, {table_var})
    all_scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(all_scans) >= 2  # super-block scan + tail mamba scan
    assert len(tainted_scans) == 1, (
        f"expected exactly one table-consuming scan, got {len(tainted_scans)}"
    )
    super_scan = tainted_scans[0]
    # the tail scan is one of the untainted ones by the assertion above

    # descend: map tainted outer invars onto the body's invars
    body = super_scan.params["jaxpr"].jaxpr
    inner_taint = {
        body.invars[i]
        for i, v in enumerate(super_scan.invars)
        if not isinstance(v, jax.extend.core.Literal) and v in tainted
    }
    assert inner_taint, "table must enter the super-block scan body"
    _, inner_tainted_scans = _propagate(body, inner_taint)
    assert not inner_tainted_scans, (
        "the inner Mamba-group scan must not consume page-table-derived "
        "values — SSM layers carry no page-table work"
    )
