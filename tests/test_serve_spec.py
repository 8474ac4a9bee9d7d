"""Self-speculative decoding on the hierarchical quantized cache
(docs/SERVING.md §11): the draft pass reads the *same* pools at a truncated
bit-width, one batched verify scan replays the feeds at full fidelity, and
the greedy exact-match acceptance rule makes the emitted stream bitwise
identical to ``spec_k = 1`` — asserted here across cache families, bit
widths, granularities, pool pressure, faults, and prefix sharing.

Also covers the kernel-level ``draft_bits`` truncated-read contract
(kernels/paged_bitdecode) and the speculative counter conservation the
invariant auditor enforces.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_kernels_bitdecode import paged_args

from repro.configs.base import smoke_config
from repro.kernels.kv_quant import ref as kq_ref
from repro.kernels.paged_bitdecode import ops as pg_ops
from repro.models.zoo import build_model
from repro.serve import FaultPlan, Request, ServeEngine
from repro.serve.audit import audit_engine

BLOCK = 32


def _model(arch, **cfg_kw):
    kw = {"kv_bits": 4, "kv_block": BLOCK}
    kw.update(cfg_kw)
    cfg = smoke_config(arch).with_(**kw)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def attn_model():
    return _model("llama3-8b")


@pytest.fixture(scope="module")
def mla_model():
    return _model("deepseek-v3-671b")


@pytest.fixture(scope="module")
def hybrid_model():
    return _model("zamba2-7b")


@pytest.fixture(scope="module")
def xlstm_model():
    return _model("xlstm-1.3b")


def _workload(cfg, n=4, seed=42, max_new=(12, 20)):
    """Block-crossing prompts so draft/verify cycles straddle residual
    flushes (the interesting part of the hierarchy)."""
    rng = np.random.default_rng(seed)
    return [
        Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab, int(rng.integers(34, 48)))
            .astype(np.int32),
            max_new_tokens=int(rng.integers(*max_new)),
        )
        for i in range(n)
    ]


def _run(model, params, reqs, **kw):
    engine = ServeEngine(model, params, slots=2, max_seq=128, **kw)
    for r in reqs:
        assert engine.submit(r)
    engine.run()
    return engine


def _assert_spec_matches_sequential(cfg, model, params, *, spec_k,
                                    spec_bits=None, n=4,
                                    max_new=(12, 20), **spec_kw):
    base_reqs = _workload(cfg, n, max_new=max_new)
    _run(model, params, base_reqs)
    seq = {r.uid: list(r.out_tokens) for r in base_reqs}
    reqs = _workload(cfg, n, max_new=max_new)
    engine = _run(model, params, reqs, spec_k=spec_k, spec_bits=spec_bits,
                  audit_every=1, **spec_kw)
    for r in reqs:
        assert r.done, (r.uid, r.phase, r.error)
        assert list(r.out_tokens) == seq[r.uid], (
            f"request {r.uid} diverged under spec_k={spec_k}"
        )
    assert engine.stats["spec_cycles"] > 0
    assert audit_engine(engine).ok
    return engine


# --------------------------------------------------------------------------
# Bitwise parity: every cache family, bits x granularity
# --------------------------------------------------------------------------

def test_spec_matches_sequential_attn_4bit_channel(attn_model):
    cfg, model, params = attn_model
    engine = _assert_spec_matches_sequential(cfg, model, params, spec_k=3)
    assert engine.spec_bits == 2  # default min(2, kv_bits)


def test_spec_matches_sequential_attn_2bit_tensor():
    """kv_bits=2 + tensor granularity: spec_bits floors at the cache width,
    so the draft read is full fidelity (draft_bits >= bits no-op path)."""
    cfg, model, params = _model("llama3-8b", kv_bits=2, kv_gran="tensor")
    engine = _assert_spec_matches_sequential(cfg, model, params, spec_k=2)
    assert engine.spec_bits == 2


def test_spec_matches_sequential_mla(mla_model):
    cfg, model, params = mla_model
    _assert_spec_matches_sequential(cfg, model, params, spec_k=2, n=3)


def test_spec_matches_sequential_hybrid(hybrid_model):
    """Hybrid per-layer states: the verify scan must freeze dead lanes'
    SSM recurrent side-state, not just the paged KV."""
    cfg, model, params = hybrid_model
    _assert_spec_matches_sequential(cfg, model, params, spec_k=2, n=3)


def test_spec_xlstm_full_acceptance(xlstm_model):
    """The recurrent shim has no quantized cache: draft and verify run the
    same full-precision math, so every draft token must be accepted."""
    cfg, model, params = xlstm_model
    engine = _assert_spec_matches_sequential(cfg, model, params, spec_k=2,
                                             n=3)
    assert engine.stats["spec_draft_tokens"] > 0
    assert engine.stats["spec_rejected_tokens"] == 0
    assert engine.summary()["spec_accept_rate"] == 1.0


# --------------------------------------------------------------------------
# Pressure, faults, prefix sharing
# --------------------------------------------------------------------------

def test_spec_under_oversubscription_and_faults(attn_model):
    """Oversubscribed pool + expected reservations + alloc-fail faults:
    preemption-by-rematerialization (teacher-forced replay lanes in the
    verify scan) must still reconstruct the sequential stream bitwise."""
    cfg, model, params = attn_model
    plan = FaultPlan(seed=5, alloc_fail=0.3)
    engine = _assert_spec_matches_sequential(
        cfg, model, params, spec_k=3, n=5, max_new=(24, 32),
        n_pages=2 + 3, reserve_policy="expected", expected_quantile=0.0,
        faults=plan,
    )
    assert engine.stats["preempted"] > 0, "no pressure exercised"
    assert engine.stats["preempt_remat_tokens"] > 0
    assert engine.pool.n_free == engine.pool.capacity
    assert engine.pool.reserved == 0


def test_spec_with_prefix_sharing(attn_model):
    """Requests sharing a long prompt prefix: shared pages + suffix prefill
    interleave with speculative cycles without breaking parity.  The
    baseline is an identically-staggered *sequential* engine: a sharer's
    suffix prefill reads dequantized committed blocks, so its stream
    legitimately differs from an unshared run — what speculation must
    preserve is the sharing run itself, bit for bit."""
    cfg, model, params = attn_model
    rng = np.random.default_rng(9)
    stem = rng.integers(0, cfg.vocab, 2 * BLOCK + 7).astype(np.int32)
    mk = lambda uid: Request(uid=uid, prompt=stem.copy(), max_new_tokens=10)

    def staggered(**kw):
        reqs = [mk(0), mk(1), mk(2)]
        engine = ServeEngine(model, params, slots=2, max_seq=128, **kw)
        engine.submit(reqs[0])
        engine.step()  # donor adopted + its prefix registered
        engine.submit(reqs[1])
        engine.submit(reqs[2])
        engine.run()
        assert engine.stats["prefill_tokens_saved"] > 0, "sharing never fired"
        return engine, reqs

    _, base_reqs = staggered()
    seq = {r.uid: list(r.out_tokens) for r in base_reqs}
    engine, reqs = staggered(spec_k=3, audit_every=1)
    for r in reqs:
        assert list(r.out_tokens) == seq[r.uid]
    assert audit_engine(engine).ok


def test_spec_poisoned_row_isolated(attn_model):
    """A poisoned cycle retires only its own request (ERRORED) mid-spec;
    unaffected requests keep sequential parity."""
    cfg, model, params = attn_model
    base_reqs = _workload(cfg)
    _run(model, params, base_reqs)
    seq = {r.uid: list(r.out_tokens) for r in base_reqs}
    plan = FaultPlan(seed=1, fire_at={"poison_logits": (3,)},
                     max_fires={"poison_logits": 1})
    reqs = _workload(cfg)
    engine = _run(model, params, reqs, spec_k=3, faults=plan, audit_every=2)
    errored = [r for r in reqs if not r.done]
    assert len(errored) == 1
    assert "non-finite logits" in errored[0].error
    assert engine.stats["errored"] == 1
    for r in reqs:
        if r is errored[0]:
            continue
        assert r.done and list(r.out_tokens) == seq[r.uid]
    assert audit_engine(engine).ok


# --------------------------------------------------------------------------
# Counters and configuration
# --------------------------------------------------------------------------

def test_spec_counters_conserved(attn_model):
    cfg, model, params = attn_model
    reqs = _workload(cfg)
    engine = _run(model, params, reqs, spec_k=3, audit_every=1)
    s = engine.stats
    assert s["spec_cycles"] > 0
    assert s["spec_draft_tokens"] == (
        s["spec_accepted_tokens"] + s["spec_rejected_tokens"]
    )
    # per-request counters sum to the engine totals (replay lanes draft
    # nothing, so retired requests account for every drafted token)
    assert sum(r.spec_accepted for r in reqs) == s["spec_accepted_tokens"]
    assert sum(r.spec_rejected for r in reqs) == s["spec_rejected_tokens"]
    assert 0.0 <= engine.summary()["spec_accept_rate"] <= 1.0
    # the decoded stream itself is fully accounted: every emitted token
    # came from exactly one applied verify feed
    assert s["decoded_tokens"] == sum(len(r.out_tokens) for r in reqs)


def test_spec_config_validation(attn_model):
    cfg, model, params = attn_model
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(model, params, spec_k=0)
    with pytest.raises(ValueError, match="spec_bits"):
        ServeEngine(model, params, spec_k=2, spec_bits=0)
    with pytest.raises(ValueError, match="spec_bits"):
        ServeEngine(model, params, spec_k=2, spec_bits=8)  # > kv_bits=4
    # spec_k=1 is plain sequential decode: no draft/verify built
    engine = ServeEngine(model, params)
    assert engine._draft is None and engine._verify is None
    assert "spec_accept_rate" not in engine.summary()


# --------------------------------------------------------------------------
# Kernel-level draft_bits contract (truncated committed-pool read)
# --------------------------------------------------------------------------

def _bd_case(key, *, bits, res_len, pack_blocks, k_gran="channel"):
    b, h, g, d, nb, block_n = 1, 2, 4, 128, 2, 128
    ks = jax.random.split(key, 5)
    k_full = jax.random.normal(ks[0], (b, h, nb * block_n, d), jnp.float32)
    v_full = jax.random.normal(ks[1], (b, h, nb * block_n, d), jnp.float32)
    q = (jax.random.normal(ks[2], (b, h, g, d), jnp.float32) / d**0.25
         ).astype(jnp.bfloat16)
    k_res = jax.random.normal(ks[3], (b, h, block_n, d),
                              jnp.float32).astype(jnp.bfloat16)
    v_res = jax.random.normal(ks[4], (b, h, block_n, d),
                              jnp.float32).astype(jnp.bfloat16)
    kw, ksc, kzp = kq_ref.quantize_kv_ref(k_full.astype(jnp.bfloat16), bits,
                                          k_gran, block_n=block_n)
    vw, vsc, vzp = kq_ref.quantize_kv_ref(v_full.astype(jnp.bfloat16), bits,
                                          "tensor", block_n=block_n)
    case = dict(q=q, kw=kw, k_scale=ksc, k_zero=kzp, vw=vw, v_scale=vsc,
                v_zero=vzp, k_res=k_res, v_res=v_res,
                pack_blocks=jnp.asarray(pack_blocks, jnp.int32),
                res_len=jnp.asarray(res_len, jnp.int32))
    return paged_args(case, bits=bits, block_n=block_n, k_gran=k_gran), block_n


def test_draft_bits_noop_when_not_truncating():
    """draft_bits >= bits reads full fidelity: bitwise the normal path."""
    case, block_n = _bd_case(jax.random.PRNGKey(0), bits=4,
                             pack_blocks=[2], res_len=[17])
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=4,
                           block_n=block_n, impl="xla")
    full = np.asarray(fn(*case))
    for db in (4, 8):
        np.testing.assert_array_equal(np.asarray(fn(*case, draft_bits=db)),
                                      full)


def test_draft_bits_truncates_committed_read_only():
    """The truncated read touches only the packed pools: with everything in
    the residual window the draft output is bitwise the full output, and
    with committed blocks present it must actually differ."""
    res_only, block_n = _bd_case(jax.random.PRNGKey(1), bits=4,
                                 pack_blocks=[0], res_len=[33])
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=4,
                           block_n=block_n, impl="xla")
    np.testing.assert_array_equal(
        np.asarray(fn(*res_only, draft_bits=2)),
        np.asarray(fn(*res_only)),
    )
    packed, _ = _bd_case(jax.random.PRNGKey(1), bits=4,
                         pack_blocks=[2], res_len=[33])
    full = np.asarray(fn(*packed))
    draft = np.asarray(fn(*packed, draft_bits=2))
    assert draft.shape == full.shape and np.isfinite(draft).all()
    assert not np.array_equal(draft, full)
    # coarser, not broken: still an attention output in the same range
    assert float(np.abs(draft - full).max()) < 1.0


def test_draft_bits_validation():
    case, block_n = _bd_case(jax.random.PRNGKey(2), bits=4,
                             pack_blocks=[1], res_len=[5])
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=4,
                           block_n=block_n)
    with pytest.raises(ValueError):
        fn(*case, impl="xla", draft_bits=0)
    with pytest.raises(ValueError, match="Pallas"):
        fn(*case, impl="pallas", draft_bits=2)
