"""Cache invariants: append/flush/prefill vs a plain fp16 history oracle.

Property tests (hypothesis) over lengths: for any number of appended tokens,
attention through the quantized cache tracks exact attention over the same
history, and the packed/residual partition always satisfies the paper's
invariants (res_len < N_r, length = pack_blocks * N_r + res_len).  Decode
reads pages: dense caches go through ``qcache.to_pages`` (identity page
table) first.
"""
import functools

import pytest

hypothesis = pytest.importorskip("hypothesis")
import hypothesis.strategies as st  # noqa: E402
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import attention as catt
from repro.core import qcache

jax.config.update("jax_platform_name", "cpu")

B, H, D, BLOCK = 2, 2, 64, 128
MAXSEQ = 4 * BLOCK


def _history(key, n):
    ks = jax.random.split(key, 2)
    k = jax.random.normal(ks[0], (B, H, n, D), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[1], (B, H, n, D), jnp.float32).astype(jnp.bfloat16)
    return k, v


def _oracle(q, k, v):
    s = jnp.einsum("bhgd,bhtd->bhgt", q.astype(jnp.float32), k.astype(jnp.float32))
    p = jax.nn.softmax(s / q.shape[-1] ** 0.5, axis=-1)
    return jnp.einsum("bhgt,bhtd->bhgd", p, v.astype(jnp.float32))


@jax.jit
def _append_all(cache, k, v):
    def body(c, kv):
        kn, vn = kv
        return qcache.paged_append_decode(c, kn[:, :, None], vn[:, :, None]), None

    cache, _ = jax.lax.scan(body, cache, (k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
    return cache


@hypothesis.given(n=st.integers(min_value=1, max_value=3 * BLOCK + 17))
@hypothesis.settings(max_examples=12, deadline=None)
def test_append_matches_history_oracle(n):
    k, v = _history(jax.random.PRNGKey(n), n)
    cache = qcache.to_pages(qcache.init_cache(B, H, D, MAXSEQ, bits=8, block_n=BLOCK))
    cache = _append_all(cache, k, v)

    # occupancy invariants (paper partition X = X_pack ∪ X_res)
    assert int(cache.res_len[0]) < BLOCK or BLOCK == int(cache.res_len[0]) == 0
    np.testing.assert_array_equal(np.asarray(cache.length), n)
    assert int(cache.pack_blocks[0]) == n // BLOCK

    q = (jax.random.normal(jax.random.PRNGKey(7 * n + 1), (B, 1, H * 2, D))).astype(jnp.bfloat16)
    out = catt.decode_attention(q, cache, impl="xla")
    # oracle over the exact same history, GQA expanded (g_q = 2)
    qt = q.reshape(B, H, 2, D)
    ref = _oracle(qt, k, v)
    np.testing.assert_allclose(
        np.asarray(out.reshape(B, H, 2, D)), np.asarray(ref), rtol=0.08, atol=0.08
    )


@hypothesis.given(n=st.integers(min_value=1, max_value=MAXSEQ - BLOCK))
@hypothesis.settings(max_examples=10, deadline=None)
def test_prefill_equals_incremental_append(n):
    """prefill(L) and L × append produce identical attention outputs."""
    k, v = _history(jax.random.PRNGKey(1000 + n), n)
    c_inc = _append_all(
        qcache.to_pages(qcache.init_cache(B, H, D, MAXSEQ, bits=4, block_n=BLOCK)), k, v)
    c_pre = qcache.to_pages(qcache.prefill(
        qcache.init_cache(B, H, D, MAXSEQ, bits=4, block_n=BLOCK), k, v, quant_impl="xla"
    ))
    np.testing.assert_array_equal(np.asarray(c_inc.pack_blocks), np.asarray(c_pre.pack_blocks))
    np.testing.assert_array_equal(np.asarray(c_inc.res_len), np.asarray(c_pre.res_len))
    q = jax.random.normal(jax.random.PRNGKey(3), (B, 1, H, D)).astype(jnp.bfloat16)
    o_inc = catt.decode_attention(q, c_inc, impl="xla")
    o_pre = catt.decode_attention(q, c_pre, impl="xla")
    np.testing.assert_allclose(np.asarray(o_inc), np.asarray(o_pre), rtol=1e-5, atol=1e-5)


def test_blockwise_attention_matches_naive():
    b, s, hq, hkv, d = 2, 192, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    out = catt.blockwise_attention(q, k, v, causal=True, block_k=64)
    # naive causal reference with GQA expansion
    kx = jnp.repeat(k, hq // hkv, axis=2)
    vx = jnp.repeat(v, hq // hkv, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, kx) / d**0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -1e37)
    ref = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(scores, axis=-1), vx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_mla_shared_cache_roundtrip():
    """Latent (shared_kv) cache: decode matches oracle on the latent stream."""
    d_lat, d_v, n = 128, 128, 200
    k = jax.random.normal(
        jax.random.PRNGKey(5), (B, H, n, d_lat), jnp.float32
    ).astype(jnp.bfloat16)
    cache = qcache.to_pages(
        qcache.init_cache(B, H, d_lat, MAXSEQ, bits=8, block_n=BLOCK, shared_kv=True))

    def body(c, kn):
        return qcache.paged_append_decode(c, kn[:, :, None], None), None

    cache, _ = jax.lax.scan(body, cache, k.transpose(2, 0, 1, 3))
    q = jax.random.normal(jax.random.PRNGKey(6), (B, 1, H * 4, d_lat)).astype(jnp.bfloat16)
    out = catt.decode_attention(q, cache, d_v=d_v, impl="xla")
    qt = q.reshape(B, H, 4, d_lat)
    ref = _oracle(qt, k, k[..., :d_v])
    np.testing.assert_allclose(
        np.asarray(out.reshape(B, H, 4, d_v)), np.asarray(ref), rtol=0.08, atol=0.08
    )


# --------------------------------------------------------------------------
# the prefill -> decode handoff: qcache.to_pages
# --------------------------------------------------------------------------

def _stacked_prefill(n_layers, lengths, *, d=D, shared_kv=False, key=0):
    """A layer-stacked dense cache as a model's prefill returns it: each
    layer prefilled from its own ragged history."""
    caches = []
    for layer in range(n_layers):
        k, v = _history(jax.random.PRNGKey(key + layer), max(lengths))
        c = qcache.init_cache(B, H, d, MAXSEQ, bits=4, block_n=BLOCK,
                              shared_kv=shared_kv)
        caches.append(qcache.prefill(c, k, None if shared_kv else v,
                                     lengths=jnp.asarray(lengths, jnp.int32),
                                     quant_impl="xla"))
    return jax.tree.map(lambda *a: jnp.stack(a), *caches)


@pytest.mark.parametrize("shared_kv", [False, True])
def test_to_pages_reads_the_dense_blocks(shared_kv):
    """Decoding a layer-stacked cache's pages (``qcache.to_pages``, layer by
    layer in place) is bitwise the reference over its dense blocks."""
    from repro.kernels.paged_bitdecode import ops as pg_ops
    from repro.kernels.paged_bitdecode import ref as pg_ref

    n_layers, lengths = 3, [2 * BLOCK + 17, BLOCK - 5]
    dense = _stacked_prefill(n_layers, lengths, shared_kv=shared_kv)
    pages = qcache.to_pages({"caches": [dense], "pos": jnp.zeros((B,), jnp.int32)})
    pc = pages["caches"][0]
    nb = dense.kw.shape[3]
    assert isinstance(pc, qcache.PagedQuantKVCache)
    assert pc.kw.shape[:2] == (n_layers, B + B * nb)
    np.testing.assert_array_equal(np.asarray(pc.pack_blocks), np.asarray(dense.pack_blocks))
    assert not np.asarray(pc.kw[:, :B]).any()  # the scratch pages
    qt = jax.random.normal(jax.random.PRNGKey(9), (B, H, 2, D)).astype(jnp.bfloat16)
    extra = dict(shared_kv=True, d_v=D // 2) if shared_kv else {}
    for layer in range(n_layers):
        one = jax.tree.map(lambda a: a[layer], dense)
        want = pg_ref.bitdecode_attention_ref(
            qt, one.kw, one.k_scale, one.k_zero, one.vw, one.v_scale, one.v_zero,
            one.k_res, one.v_res, one.pack_blocks, one.res_len, bits=4,
            block_n=BLOCK, **extra)
        got = pg_ops.paged_bitdecode_attention(
            qt, pc.kw, pc.k_scale, pc.k_zero, pc.vw, pc.v_scale, pc.v_zero,
            pc.k_res[layer], None if shared_kv else pc.v_res[layer],
            pc.page_table[layer], pc.pack_blocks[layer], pc.res_len[layer],
            layer=layer, bits=4, block_n=BLOCK, impl="xla", return_lse=True,
            **extra)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_decode_of_a_prefill_cache_names_to_pages():
    """A decode step handed model.prefill's dense state raises a TypeError
    that names the conversion; the converted state decodes."""
    from repro.configs.base import smoke_config
    from repro.models.zoo import build_model

    model = build_model(smoke_config("llama3-8b").with_(kv_bits=4, kv_block=32))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((B, 40), jnp.int32)
    _, state = jax.eval_shape(
        lambda p, t: model.prefill(p, {"tokens": t}, 128), params, toks)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    with pytest.raises(TypeError, match=r"qcache\.to_pages"):
        jax.eval_shape(model.decode_step, params, state, tok)
    logits, _ = jax.eval_shape(
        lambda p, s, t: model.decode_step(p, qcache.to_pages(s), t),
        params, state, tok)
    assert logits.shape[:2] == (B, 1)


def test_identity_table_flush_leaves_scratch_pages():
    """A flush through an identity-table state commits the full sequence's
    block into its own page and leaves the scratch pages ``[0, B)`` (where
    the other sequence parks) bitwise untouched."""
    k, v = _history(jax.random.PRNGKey(11), BLOCK + 1)
    cache = qcache.to_pages(qcache.prefill(
        qcache.init_cache(B, H, D, MAXSEQ, bits=4, block_n=BLOCK), k[:, :, :BLOCK],
        v[:, :, :BLOCK], lengths=jnp.asarray([BLOCK - 1, 5], jnp.int32),
        quant_impl="xla"))
    before = {f: np.asarray(getattr(cache, f)) for f in ("kw", "k_scale", "vw", "v_zero")}
    step = jax.jit(functools.partial(qcache.paged_append_decode, quant_impl="pallas"))
    out = step(cache, k[:, :, BLOCK:], v[:, :, BLOCK:])
    assert [int(x) for x in out.pack_blocks] == [1, 0]
    page = int(cache.page_table[0, 0])
    assert page >= B
    for f, b4 in before.items():
        after = np.asarray(getattr(out, f))
        np.testing.assert_array_equal(after[:B], b4[:B], err_msg=f)
        others = np.arange(cache.n_pages) != page
        np.testing.assert_array_equal(after[others], b4[others], err_msg=f)
        assert (after[page] != b4[page]).any(), f
