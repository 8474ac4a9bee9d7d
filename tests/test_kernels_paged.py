"""Paged (Page-setting) kernel vs oracle, including shared page pools with
scrambled page tables and per-sequence lengths, the shared_kv (MLA
latent-pool) parity grid, and scrambled tables reading exactly what the
identity table of ``qcache.to_pages`` reads."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qcache
from repro.kernels.kv_quant import ref as kq_ref
from repro.kernels.paged_bitdecode import ops as pg_ops


def _make(key, *, b, h, g, d, n_pages, nb, block_n, bits, k_gran):
    ks = jax.random.split(key, 6)
    # quantize a pool of pages from random K/V content
    k = jax.random.normal(ks[0], (1, h, n_pages * block_n, d), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[1], (1, h, n_pages * block_n, d), jnp.float32).astype(jnp.bfloat16)
    kw, ksc, kzp = kq_ref.quantize_kv_ref(k, bits, k_gran, block_n=block_n)
    vw, vsc, vzp = kq_ref.quantize_kv_ref(v, bits, "tensor", block_n=block_n)
    # pools: [P, H, ...]
    pool = lambda x: jnp.moveaxis(x[0], 1, 0)  # noqa: E731
    q = jax.random.normal(ks[2], (b, h, g, d), jnp.float32).astype(jnp.bfloat16)
    k_res = jax.random.normal(ks[3], (b, h, block_n, d), jnp.float32).astype(jnp.bfloat16)
    v_res = jax.random.normal(ks[4], (b, h, block_n, d), jnp.float32).astype(jnp.bfloat16)
    table = jax.random.permutation(ks[5], n_pages)[: b * nb].reshape(b, nb).astype(jnp.int32)
    return (q, pool(kw), pool(ksc), pool(kzp), pool(vw), pool(vsc), pool(vzp),
            k_res, v_res, table)


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
def test_paged_matches_ref(bits, k_gran):
    b, h, g, d, block_n, nb, n_pages = 2, 2, 8, 128, 128, 3, 8
    args = _make(jax.random.PRNGKey(0), b=b, h=h, g=g, d=d, n_pages=n_pages,
                 nb=nb, block_n=block_n, bits=bits, k_gran=k_gran)
    pb = jnp.asarray([nb, nb - 1], jnp.int32)
    rl = jnp.asarray([17, 0], jnp.int32)
    fn = functools.partial(
        pg_ops.paged_bitdecode_attention, bits=bits, block_n=block_n,
        k_gran=k_gran, return_lse=True,
    )
    out_p, lse_p = fn(*args, pb, rl, impl="pallas")
    out_r, lse_r = fn(*args, pb, rl, impl="xla")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(lse_p), np.asarray(lse_r), rtol=1e-3, atol=1e-3)


def identity_layout(pools, table, pack_blocks, res_len, k_res, v_res, **static):
    """The blocks a (scrambled) ``table`` names, gathered into a dense cache
    and laid out again by ``qcache.to_pages``: the same content under the
    identity page table.  Returns the pool and table arguments in
    ``paged_bitdecode_attention``'s order, from the pools to ``res_len``."""
    gather = lambda x: None if x is None else jnp.moveaxis(  # noqa: E731
        jnp.take(x, table, axis=0), 2, 1)
    dense = qcache.QuantKVCache(
        *(gather(p) for p in pools), k_res=k_res, v_res=v_res,
        pack_blocks=pack_blocks, res_len=res_len, **static)
    pc = qcache.to_pages(dense)
    assert not np.array_equal(np.asarray(pc.page_table), np.asarray(table))
    return (pc.kw, pc.k_scale, pc.k_zero, pc.vw, pc.v_scale, pc.v_zero,
            pc.k_res, pc.v_res, pc.page_table, pc.pack_blocks, pc.res_len)


def test_paged_equals_dense_on_same_blocks():
    """A scrambled page table reads bitwise what the identity table over the
    same blocks reads (the dense layout ``qcache.to_pages`` builds)."""
    b, h, g, d, block_n, nb, n_pages = 2, 2, 4, 128, 128, 3, 8
    args = _make(jax.random.PRNGKey(1), b=b, h=h, g=g, d=d, n_pages=n_pages,
                 nb=nb, block_n=block_n, bits=4, k_gran="channel")
    (q, kwp, ksp, kzp, vwp, vsp, vzp, k_res, v_res, table) = args
    pb = jnp.asarray([nb, nb - 1], jnp.int32)
    rl = jnp.asarray([9, 0], jnp.int32)
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=4,
                           block_n=block_n, impl="pallas")
    out_s = fn(q, kwp, ksp, kzp, vwp, vsp, vzp, k_res, v_res, table, pb, rl)
    out_i = fn(q, *identity_layout(
        (kwp, ksp, kzp, vwp, vsp, vzp), table, pb, rl, k_res, v_res,
        bits=4, block_n=block_n, k_gran="channel", shared_kv=False))
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_i))


# --------------------------------------------------------------------------
# shared_kv (MLA latent pools): paged walk vs the dense shared_kv oracle
# --------------------------------------------------------------------------

def _make_shared(key, *, b, h, g, d, n_pages, nb, block_n, bits, k_gran):
    """Latent pool set (no V side) + scrambled table + latent residual."""
    ks = jax.random.split(key, 4)
    lat = jax.random.normal(
        ks[0], (1, h, n_pages * block_n, d), jnp.float32).astype(jnp.bfloat16)
    kw, ksc, kzp = kq_ref.quantize_kv_ref(lat, bits, k_gran, block_n=block_n)
    pool = lambda x: jnp.moveaxis(x[0], 1, 0)  # noqa: E731
    q = jax.random.normal(ks[1], (b, h, g, d), jnp.float32).astype(jnp.bfloat16)
    k_res = jax.random.normal(
        ks[2], (b, h, block_n, d), jnp.float32).astype(jnp.bfloat16)
    table = jax.random.permutation(ks[3], n_pages)[: b * nb].reshape(b, nb).astype(jnp.int32)
    return q, pool(kw), pool(ksc), pool(kzp), k_res, table


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
@pytest.mark.parametrize("num_splits", [1, 2])
@pytest.mark.parametrize("res_len", [0, 17])  # empty vs partial residual
def test_paged_shared_kv_matches_ref(bits, k_gran, num_splits, res_len):
    """The satellite grid: bits x granularity x num_splits x partial
    residual — paged shared_kv Pallas vs the gather-then-reference oracle."""
    b, h, g, d, dv, block_n, nb, n_pages = 2, 1, 8, 256, 128, 64, 3, 8
    q, kwp, ksp, kzp, k_res, table = _make_shared(
        jax.random.PRNGKey(2), b=b, h=h, g=g, d=d, n_pages=n_pages, nb=nb,
        block_n=block_n, bits=bits, k_gran=k_gran)
    pb = jnp.asarray([nb, nb - 1], jnp.int32)
    rl = jnp.asarray([res_len, 0], jnp.int32)
    fn = functools.partial(
        pg_ops.paged_bitdecode_attention, bits=bits, block_n=block_n,
        k_gran=k_gran, shared_kv=True, d_v=dv, return_lse=True,
    )
    out_p, lse_p = fn(q, kwp, ksp, kzp, None, None, None, k_res, None,
                      table, pb, rl, impl="pallas", num_splits=num_splits)
    out_r, lse_r = fn(q, kwp, ksp, kzp, None, None, None, k_res, None,
                      table, pb, rl, impl="xla", num_splits=num_splits)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(lse_p), np.asarray(lse_r),
                               rtol=1e-3, atol=1e-3)


def test_paged_shared_kv_equals_dense_shared_oracle():
    """Paged shared_kv over a scrambled table == the same walk over the
    identity table of the table-gathered blocks (bitwise: same compute,
    same order)."""
    b, h, g, d, dv, block_n, nb, n_pages = 2, 1, 8, 256, 128, 64, 3, 8
    q, kwp, ksp, kzp, k_res, table = _make_shared(
        jax.random.PRNGKey(3), b=b, h=h, g=g, d=d, n_pages=n_pages, nb=nb,
        block_n=block_n, bits=4, k_gran="channel")
    pb = jnp.asarray([nb, nb - 1], jnp.int32)
    rl = jnp.asarray([9, 0], jnp.int32)
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=4,
                           block_n=block_n, shared_kv=True, d_v=dv, impl="pallas")
    out_s = fn(q, kwp, ksp, kzp, None, None, None, k_res, None, table, pb, rl)
    out_i = fn(q, *identity_layout(
        (kwp, ksp, kzp, None, None, None), table, pb, rl, k_res, None,
        bits=4, block_n=block_n, k_gran="channel", shared_kv=True))
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_i))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_shared_flush_commits_latent_through_table(impl):
    """Shared-kv paged flush: a filled latent residual commits into the pool
    page its table points at, bitwise-identical packing to a direct
    quantization of the same content; other pages untouched."""
    import dataclasses

    from repro.core import qcache

    B, H, D, BLOCK = 2, 1, 256, 64
    k = jax.random.normal(jax.random.PRNGKey(4), (B, H, BLOCK, D)).astype(jnp.bfloat16)
    pc = qcache.init_paged_cache(8, B, H, D, 3, bits=4, block_n=BLOCK,
                                 shared_kv=True)
    assert pc.vw is None and pc.v_res is None
    table = np.asarray(pc.page_table).copy()
    table[1, 0] = 5
    pc = dataclasses.replace(
        pc, page_table=jnp.asarray(table),
        k_res=pc.k_res.at[1, :, : BLOCK - 1].set(k[1, :, : BLOCK - 1]),
        res_len=jnp.asarray([3, BLOCK - 1], jnp.int32),
    )
    pc2 = qcache.paged_append_decode(
        pc, k[:, :, BLOCK - 1 : BLOCK], None, quant_impl=impl)
    assert int(pc2.pack_blocks[1]) == 1 and int(pc2.res_len[1]) == 0
    kw_want, ks_want, _ = kq_ref.quantize_kv_ref(
        np.asarray(pc2.k_res[1])[None], 4, "channel", block_n=BLOCK)
    np.testing.assert_array_equal(np.asarray(pc2.kw[5]),
                                  np.asarray(kw_want)[0, :, 0])
    np.testing.assert_array_equal(np.asarray(pc2.k_scale[5]),
                                  np.asarray(ks_want)[0, :, 0])
    assert not np.asarray(pc2.kw[6]).any()  # untouched page
    assert not np.asarray(pc2.kw[0]).any()  # slot 0's scratch page


# --------------------------------------------------------------------------
# layer-stacked pools: the decode layer scan's layout ([L, P, H, ...] plus a
# layer index) reads exactly what one layer's own pools read
# --------------------------------------------------------------------------

def stack_at(key, pools, n_layers, layer):
    """Stack each pool as layer ``layer`` of ``n_layers``; the other layers
    hold other content, so a read or write of the wrong layer shows."""
    def one(i, p):
        k = jax.random.fold_in(key, i)
        if p.dtype == jnp.int32:
            other = jax.random.randint(k, (n_layers, *p.shape), 0, 1 << 30, jnp.int32)
        else:
            other = jax.random.uniform(k, (n_layers, *p.shape), jnp.float32,
                                       0.5, 2.0).astype(p.dtype)
        return other.at[layer].set(p)

    return [None if p is None else one(i, p) for i, p in enumerate(pools)]


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("num_splits", [1, 4])
@pytest.mark.parametrize("shared_kv", [False, True])
def test_stacked_pool_read_equals_layer_pool(shared_kv, num_splits, layer, impl):
    """paged_bitdecode on layer ``layer`` of a 3-layer stack is bitwise the
    call on that layer's own pools (the first and the last layer)."""
    b, h, g, d, block_n, nb, n_pages = 2, 2, 8, 128, 128, 4, 10
    if shared_kv:
        q, kwp, ksp, kzp, k_res, table = _make_shared(
            jax.random.PRNGKey(5), b=b, h=1, g=g, d=2 * d, n_pages=n_pages,
            nb=nb, block_n=block_n, bits=4, k_gran="channel")
        pools, v_res, extra = (kwp, ksp, kzp, None, None, None), None, dict(
            shared_kv=True, d_v=d)
    else:
        (q, kwp, ksp, kzp, vwp, vsp, vzp, k_res, v_res, table) = _make(
            jax.random.PRNGKey(5), b=b, h=h, g=g, d=d, n_pages=n_pages, nb=nb,
            block_n=block_n, bits=4, k_gran="channel")
        pools, extra = (kwp, ksp, kzp, vwp, vsp, vzp), {}
    pb = jnp.asarray([nb, nb - 2], jnp.int32)
    rl = jnp.asarray([17, 5], jnp.int32)
    fn = functools.partial(
        pg_ops.paged_bitdecode_attention, bits=4, block_n=block_n,
        k_gran="channel", return_lse=True, impl=impl, num_splits=num_splits,
        **extra)
    want = fn(q, *pools, k_res, v_res, table, pb, rl)
    stacked = stack_at(jax.random.PRNGKey(6), pools, 3, layer)
    got = fn(q, *stacked, k_res, v_res, table, pb, rl,
             layer=jnp.int32(layer))
    for w, o in zip(want, got):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(w))
