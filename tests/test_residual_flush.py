"""Residual-flush kernel family: parity, boundary fills, and hot-path gating.

The contract under test (ISSUE 2 / paper §V-B): `append_decode` must produce
caches identical to the old speculative path, the Pallas flush must match the
select-based XLA oracle bitwise, and — the point of the fusion — a non-full
residual append must perform **no** quantize/pack work (the flush runs only
under the `lax.cond` taken when some sequence's residual just filled).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attention as catt
from repro.core import qcache
from repro.kernels.residual_flush import ops as rf_ops

jax.config.update("jax_platform_name", "cpu")

B, H, D, BLOCK = 2, 2, 64, 32
MAXSEQ = 4 * BLOCK

_CACHE_FIELDS = ("kw", "k_scale", "k_zero", "vw", "v_scale", "v_zero",
                 "k_res", "v_res", "pack_blocks", "res_len")


def _tokens(n, d=D, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 2)
    k = jax.random.normal(ks[0], (B, H, n, d), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[1], (B, H, n, d), jnp.float32).astype(jnp.bfloat16)
    return k, v


def _assert_caches_equal(a, b):
    for f in _CACHE_FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        if x is None:
            assert y is None
            continue
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=f)


def _append_n(cache, k, v, n, fn):
    for i in range(n):
        vi = None if cache.shared_kv else v[:, :, i : i + 1]
        cache = fn(cache, k[:, :, i : i + 1], vi)
    return cache


# ---------------------------------------------------------------- op parity


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
@pytest.mark.parametrize("shared_kv", [False, True])
def test_flush_op_pallas_matches_xla(bits, k_gran, shared_kv):
    """residual_flush: Pallas (interpret) == select-based XLA oracle, with a
    mixed full/not-full batch so both kernel branches execute."""
    cache = qcache.init_cache(
        B, H, D, MAXSEQ, bits=bits, block_n=BLOCK, k_gran=k_gran,
        shared_kv=shared_kv,
    )
    k, v = _tokens(BLOCK, key=bits)
    kres = k
    vres = None if shared_kv else v
    full = jnp.array([1, 0], jnp.int32)
    dest = jnp.array([1, 2], jnp.int32)
    args = (cache.kw, cache.k_scale, cache.k_zero, cache.vw, cache.v_scale,
            cache.v_zero, kres, vres, full, dest)
    kw = dict(bits=bits, block_n=BLOCK, k_gran=k_gran, shared_kv=shared_kv)
    ref = rf_ops.residual_flush(*args, impl="xla", **kw)
    out = rf_ops.residual_flush(*args, impl="pallas", **kw)
    for r, o, name in zip(ref, out, ("kw", "ks", "kz", "vw", "vs", "vz")):
        if r is None:
            assert o is None
            continue
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o), err_msg=name)
    # the not-full sequence's cache must be untouched
    np.testing.assert_array_equal(np.asarray(out[0][1]), np.asarray(cache.kw[1]))
    # the full sequence committed a non-trivial block at dest
    assert np.asarray(out[0][0, :, 1]).any()


# ------------------------------------------------------- append boundaries


@pytest.mark.parametrize("quant_impl", ["xla", "pallas"])
@pytest.mark.parametrize("n", [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK])
def test_append_fill_boundaries(n, quant_impl):
    """Fill counts {0, 1, N_r-1, N_r} plus flush-immediately-followed-by-
    append: gated append == speculative oracle, field for field."""
    k, v = _tokens(max(n, 1), key=7 * n + 1)
    gated = jax.jit(functools.partial(qcache.append_decode, quant_impl=quant_impl))
    spec = jax.jit(
        functools.partial(qcache.append_decode_speculative, quant_impl="xla")
    )
    c_g = _append_n(
        qcache.init_cache(B, H, D, MAXSEQ, bits=4, block_n=BLOCK), k, v, n, gated
    )
    c_s = _append_n(
        qcache.init_cache(B, H, D, MAXSEQ, bits=4, block_n=BLOCK), k, v, n, spec
    )
    _assert_caches_equal(c_g, c_s)
    assert int(c_g.pack_blocks[0]) == n // BLOCK
    assert int(c_g.res_len[0]) == n % BLOCK
    np.testing.assert_array_equal(np.asarray(c_g.length), n)


def test_flush_then_append_attention_parity():
    """Attention over a cache that flushed and then appended again matches
    the fp16 history oracle."""
    n = BLOCK + 3
    k, v = _tokens(n, key=11)
    cache = qcache.init_cache(B, H, D, MAXSEQ, bits=8, block_n=BLOCK)
    cache = _append_n(
        cache, k, v, n, functools.partial(qcache.append_decode, quant_impl="xla")
    )
    q = jax.random.normal(jax.random.PRNGKey(3), (B, 1, H * 2, D)).astype(jnp.bfloat16)
    out = catt.decode_attention(q, cache, impl="xla")
    qt = q.reshape(B, H, 2, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhtd->bhgt", qt, k.astype(jnp.float32))
    p = jax.nn.softmax(s / D**0.5, axis=-1)
    ref = jnp.einsum("bhgt,bhtd->bhgd", p, v.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(out.reshape(B, H, 2, D)), np.asarray(ref), rtol=0.08, atol=0.08
    )


def test_append_2bit_channel_shared_kv():
    """2-bit channel-wise latent (shared_kv) cache across a flush boundary:
    gated == speculative and occupancy invariants hold."""
    n = BLOCK + 2
    k, _ = _tokens(n, d=D, key=13)
    gated = jax.jit(functools.partial(qcache.append_decode, quant_impl="pallas"))
    spec = jax.jit(
        functools.partial(qcache.append_decode_speculative, quant_impl="xla")
    )
    mk = functools.partial(
        qcache.init_cache, B, H, D, MAXSEQ, bits=2, block_n=BLOCK,
        k_gran="channel", shared_kv=True,
    )
    c_g = _append_n(mk(), k, None, n, gated)
    c_s = _append_n(mk(), k, None, n, spec)
    _assert_caches_equal(c_g, c_s)
    assert int(c_g.pack_blocks[0]) == 1 and int(c_g.res_len[0]) == 2


def test_staggered_flush_across_batch():
    """Sequences flushing on different steps (per-sequence res_len) stay
    consistent with the speculative oracle."""
    k, v = _tokens(BLOCK, key=17)
    pre_k, pre_v = _tokens(3, key=19)
    base = qcache.init_cache(B, H, D, MAXSEQ, bits=4, block_n=BLOCK)
    # stagger: sequence 0 starts 3 tokens ahead (per-row prefill splice)
    def stagger(c):
        filled = qcache.prefill(
            qcache.init_cache(B, H, D, MAXSEQ, bits=4, block_n=BLOCK),
            pre_k, pre_v, quant_impl="xla",
        )
        return dataclasses.replace(
            c,
            k_res=c.k_res.at[0].set(filled.k_res[0]),
            v_res=c.v_res.at[0].set(filled.v_res[0]),
            res_len=c.res_len.at[0].set(3),
        )

    gated = jax.jit(functools.partial(qcache.append_decode, quant_impl="xla"))
    spec = jax.jit(
        functools.partial(qcache.append_decode_speculative, quant_impl="xla")
    )
    c_g = _append_n(stagger(base), k, v, BLOCK, gated)
    c_s = _append_n(stagger(base), k, v, BLOCK, spec)
    _assert_caches_equal(c_g, c_s)
    # sequence 0 flushed 3 tokens earlier
    assert int(c_g.pack_blocks[0]) == 1 and int(c_g.res_len[0]) == 3
    assert int(c_g.pack_blocks[1]) == 1 and int(c_g.res_len[1]) == 0


# ---------------------------------------------------------------- gating


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
def test_hot_path_does_no_quant_work(quant_impl):
    """The acceptance criterion: quantize/pack work lives exclusively inside
    the flush branch of a single `cond`; the per-token path traced at the
    top level carries none of it."""
    cache = qcache.init_cache(B, H, D, MAXSEQ, bits=4, block_n=BLOCK)
    k, v = _tokens(1)
    jaxpr = jax.make_jaxpr(
        functools.partial(qcache.append_decode, quant_impl=quant_impl)
    )(cache, k, v)
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
