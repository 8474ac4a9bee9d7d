"""Residual-flush kernel family: parity, boundary fills, and hot-path gating.

The contract under test (paper §V-B): ``qcache.paged_append_decode`` over a
dense cache's pages (the identity page table of ``qcache.to_pages``) holds
exactly the token history appended to it — committed blocks bitwise as the
quantization of that history, the rest in the residual; the Pallas flush
matches the select-based XLA oracle bitwise; and — the point of the fusion —
a non-full residual append performs **no** quantize/pack work (it sits
behind the flush's per-row gate).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attention as catt
from repro.core import qcache
from repro.kernels.kv_quant import ref as kq_ref
from repro.kernels.residual_flush import ops as rf_ops

jax.config.update("jax_platform_name", "cpu")

B, H, D, BLOCK = 2, 2, 64, 32
MAXSEQ = 4 * BLOCK

_POOLS = ("kw", "k_scale", "k_zero", "vw", "v_scale", "v_zero")


def _tokens(n, d=D, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 2)
    k = jax.random.normal(ks[0], (B, H, n, d), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[1], (B, H, n, d), jnp.float32).astype(jnp.bfloat16)
    return k, v


def _pages(**kw):
    """An empty dense cache of B sequences under its identity page table."""
    kw = {"bits": 4, "block_n": BLOCK, **kw}
    return qcache.to_pages(qcache.init_cache(B, H, D, MAXSEQ, **kw))


def _append_n(cache, k, v, n, fn):
    for i in range(n):
        vi = None if cache.shared_kv else v[:, :, i : i + 1]
        cache = fn(cache, k[:, :, i : i + 1], vi)
    return cache


def _assert_holds_history(cache, k_hist, v_hist):
    """The per-token history oracle: sequence b's history ``k_hist[b]``
    (``[H, n_b, d]``) sits in ``cache`` as its first ``n_b // BLOCK`` blocks,
    quantized bitwise as quantizing the history directly, in the pages its
    table names, and its last ``n_b % BLOCK`` tokens in the residual."""
    table = np.asarray(cache.page_table)
    sides = [("kw", "k_scale", "k_zero", "k_res", cache.k_gran, k_hist)]
    if not cache.shared_kv:
        sides.append(("vw", "v_scale", "v_zero", "v_res", "tensor", v_hist))
    for b in range(B):
        n = k_hist[b].shape[1]
        nblk, rl = n // BLOCK, n % BLOCK
        assert int(cache.pack_blocks[b]) == nblk and int(cache.res_len[b]) == rl
        assert int(cache.length[b]) == n
        for w_f, s_f, z_f, r_f, gran, hist in sides:
            x = hist[b]
            if nblk:
                want = kq_ref.quantize_kv_ref(x[None, :, : nblk * BLOCK], cache.bits,
                                              gran, block_n=BLOCK)
                for f, w in zip((w_f, s_f, z_f), want):
                    got = np.asarray(getattr(cache, f))[table[b, :nblk]]
                    np.testing.assert_array_equal(
                        np.moveaxis(got, 0, 1), np.asarray(w)[0], err_msg=f)
            np.testing.assert_array_equal(
                np.asarray(getattr(cache, r_f))[b, :, :rl],
                np.asarray(x[:, nblk * BLOCK :]), err_msg=r_f)


# ---------------------------------------------------------------- op parity


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
@pytest.mark.parametrize("shared_kv", [False, True])
def test_flush_op_pallas_matches_xla(bits, k_gran, shared_kv):
    """paged_residual_flush on an identity page table: Pallas (interpret) ==
    select-based XLA oracle, with a mixed full/not-full batch so both kernel
    branches execute."""
    cache = _pages(bits=bits, k_gran=k_gran, shared_kv=shared_kv)
    k, v = _tokens(BLOCK, key=bits)
    vres = None if shared_kv else v
    full = jnp.array([1, 0], jnp.int32)
    page = int(cache.page_table[0, 1])  # sequence 0's block 1
    dest = jnp.array([page, 1], jnp.int32)  # sequence 1 parks on its scratch page
    pools, _ = qcache.split_pools(cache)
    kw = dict(bits=bits, block_n=BLOCK, k_gran=k_gran, shared_kv=shared_kv)
    ref = rf_ops.paged_residual_flush(*pools, k, vres, full, dest, impl="xla", **kw)
    out = rf_ops.paged_residual_flush(*pools, k, vres, full, dest, impl="pallas", **kw)
    for r, o, name in zip(ref, out, _POOLS):
        if r is None:
            assert o is None
            continue
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o), err_msg=name)
    # every page but the full sequence's destination is untouched
    others = np.arange(cache.n_pages) != page
    np.testing.assert_array_equal(np.asarray(out[0])[others],
                                  np.asarray(cache.kw)[others])
    # the full sequence committed a non-trivial block at dest
    assert np.asarray(out[0][page]).any()


# ------------------------------------------------------- append boundaries


@pytest.mark.parametrize("quant_impl", ["xla", "pallas"])
@pytest.mark.parametrize("n", [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK])
def test_append_fill_boundaries(n, quant_impl):
    """Fill counts {0, 1, N_r-1, N_r} plus flush-immediately-followed-by-
    append: the gated paged append holds exactly the appended history."""
    k, v = _tokens(max(n, 1), key=7 * n + 1)
    gated = jax.jit(functools.partial(qcache.paged_append_decode,
                                      quant_impl=quant_impl))
    c_g = _append_n(_pages(), k, v, n, gated)
    _assert_holds_history(c_g, k[:, :, :n], v[:, :, :n])


def test_flush_then_append_attention_parity():
    """Attention over a cache that flushed and then appended again matches
    the fp16 history oracle."""
    n = BLOCK + 3
    k, v = _tokens(n, key=11)
    cache = _append_n(
        _pages(bits=8), k, v, n,
        functools.partial(qcache.paged_append_decode, quant_impl="xla"),
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
    the gated append holds the history and occupancy invariants hold."""
    n = BLOCK + 2
    k, _ = _tokens(n, d=D, key=13)
    gated = jax.jit(functools.partial(qcache.paged_append_decode,
                                      quant_impl="pallas"))
    c_g = _append_n(_pages(bits=2, k_gran="channel", shared_kv=True), k, None, n,
                    gated)
    assert c_g.vw is None and c_g.v_res is None
    _assert_holds_history(c_g, k, None)
    assert int(c_g.pack_blocks[0]) == 1 and int(c_g.res_len[0]) == 2


def test_staggered_flush_across_batch():
    """Sequences flushing on different steps (per-sequence res_len) each
    hold their own history."""
    k, v = _tokens(BLOCK, key=17)
    pre_k, pre_v = _tokens(3, key=19)
    # stagger: sequence 0 starts 3 tokens ahead (per-row prefill splice)
    filled = qcache.to_pages(qcache.prefill(
        qcache.init_cache(B, H, D, MAXSEQ, bits=4, block_n=BLOCK),
        pre_k, pre_v, quant_impl="xla",
    ))
    base = _pages()
    base = dataclasses.replace(
        base,
        k_res=base.k_res.at[0].set(filled.k_res[0]),
        v_res=base.v_res.at[0].set(filled.v_res[0]),
        res_len=base.res_len.at[0].set(3),
    )
    gated = jax.jit(functools.partial(qcache.paged_append_decode, quant_impl="xla"))
    c_g = _append_n(base, k, v, BLOCK, gated)

    def history(pre, x):  # per sequence: [H, n_b, d]
        return [jnp.concatenate([pre[0], x[0]], axis=1), x[1]]

    _assert_holds_history(c_g, history(pre_k, k), history(pre_v, v))
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


def _find_eqns(jaxpr, name):
    """The equations named ``name`` in ``jaxpr`` and its sub-jaxprs."""
    from jax.extend import core as jc

    for e in jaxpr.eqns:
        if e.primitive.name == name:
            yield e
        for val in e.params.values():
            for w in val if isinstance(val, (tuple, list)) else (val,):
                if isinstance(w, jc.ClosedJaxpr):
                    yield from _find_eqns(w.jaxpr, name)


@pytest.mark.parametrize("quant_impl", ["xla", "pallas"])
def test_hot_path_does_no_quant_work(quant_impl):
    """The acceptance criterion, through the model-facing hot path
    (``decode_append_attention``: append + flush + attention): quantize/pack
    work lives exclusively inside the flush's gate — one branch of the XLA
    reference's ``cond``, or the Pallas flush kernel's per-row
    ``pl.when(full)`` — and the attention carries none of it."""
    cache = _pages()
    k, v = _tokens(1)
    q = jnp.ones((B, 1, H * 2, D), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(functools.partial(
        catt.decode_append_attention, quant_impl=quant_impl, impl=quant_impl,
    ))(q, cache, k, v)
    top = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert "shift_left" not in top and "round" not in top
    if quant_impl == "pallas":
        # the kernels sit inside their jitted wrappers; _collect_prims walks
        # those but never into a kernel body
        assert "shift_left" not in _collect_prims(jaxpr.jaxpr, set())
        kernels = {e.params["name"]: e
                   for e in _find_eqns(jaxpr.jaxpr, "pallas_call")}
        assert set(kernels) == {"paged_residual_flush", "paged_bitdecode"}
        assert "shift_left" not in _collect_prims(
            kernels["paged_bitdecode"].params["jaxpr"], set())
        body = kernels["paged_residual_flush"].params["jaxpr"]
    else:
        assert "pallas_call" not in _collect_prims(jaxpr.jaxpr, set())
        body = jaxpr.jaxpr
    conds = [e for e in body.eqns if e.primitive.name == "cond"]
    branch_has_quant = [
        "shift_left" in _collect_prims(br.jaxpr, set())
        for c in conds for br in c.params["branches"]
    ]
    assert sum(branch_has_quant) == 1, branch_has_quant


# ------------------------------------------------- layer-stacked paged flush


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("shared_kv", [False, True])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_stacked_paged_flush_writes_only_its_layer(impl, shared_kv, layer):
    """The paged flush into layer ``layer`` of a 3-layer pool stack writes
    exactly the pages of that layer its full rows flush to, as the flush
    into that layer's own pools does; every other page of every layer, the
    non-full row's scratch page included, stays bitwise as it was."""
    from test_kernels_paged import stack_at

    b, n_pages, n_layers = 3, 9, 3
    one = qcache.init_paged_cache(n_pages, b, H, D, 4, bits=4, block_n=BLOCK,
                                  shared_kv=shared_kv)
    pools, _ = qcache.split_pools(one)
    stacked = stack_at(jax.random.PRNGKey(1), pools, n_layers, layer)
    pools = [None if p is None else p[layer] for p in stacked]  # non-zero
    k = jax.random.normal(jax.random.PRNGKey(2), (b, H, BLOCK, D)).astype(jnp.bfloat16)
    v = None if shared_kv else (k * 0.5 + 1).astype(jnp.bfloat16)
    full = jnp.asarray([1, 0, 1], jnp.int32)
    dest = jnp.asarray([7, 1, 5], jnp.int32)  # row 1 parks on its scratch page
    kw = dict(bits=4, block_n=BLOCK, k_gran="channel", shared_kv=shared_kv,
              impl=impl)
    want = rf_ops.paged_residual_flush(*pools, k, v, full, dest, **kw)
    got = rf_ops.paged_residual_flush(*stacked, k, v, full, dest,
                                      layer=jnp.int32(layer), **kw)
    written = np.zeros((n_layers, n_pages), bool)
    written[layer, [7, 5]] = True
    for before, w, g in zip(stacked, want, got):
        if before is None:
            assert w is None and g is None
            continue
        g, before = np.asarray(g), np.asarray(before)
        np.testing.assert_array_equal(g[layer], np.asarray(w))
        np.testing.assert_array_equal(g[~written], before[~written])
        assert (g[written] != before[written]).any()
