"""Split-KV decode parity: the two-phase (per-split partials + lse merge)
kernel is policy-equivalent to the unsplit kernel and the ref oracle for any
num_splits — across bits, K-param granularity, shared-KV (MLA) mode, splits
that cover zero valid blocks (finalize's l=0 / lse=-inf guard), and a
partially filled residual.  Dense blocks walk through the identity page
table of ``qcache.to_pages``; shuffled tables are covered too."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_kernels_bitdecode import paged_args

from repro.kernels.kv_quant import ref as kq_ref
from repro.kernels.paged_bitdecode import ops as pg_ops


def _make_case(key, *, b, h, g, d_k, d_v, nb, block_n, bits, k_gran,
               pack_blocks, res_len):
    ks = jax.random.split(key, 6)
    s_pack = nb * block_n
    k_full = jax.random.normal(ks[0], (b, h, s_pack, d_k), jnp.float32)
    k_full += 2.0 * jax.random.normal(ks[5], (d_k,), jnp.float32)
    v_full = jax.random.normal(ks[1], (b, h, s_pack, d_v), jnp.float32)
    q = (jax.random.normal(ks[2], (b, h, g, d_k), jnp.float32) / d_k**0.25
         ).astype(jnp.bfloat16)
    k_res = jax.random.normal(ks[3], (b, h, block_n, d_k), jnp.float32
                              ).astype(jnp.bfloat16)
    v_res = jax.random.normal(ks[4], (b, h, block_n, d_v), jnp.float32
                              ).astype(jnp.bfloat16)
    kw, ksc, kzp = kq_ref.quantize_kv_ref(
        k_full.astype(jnp.bfloat16), bits, k_gran, block_n=block_n)
    vw, vsc, vzp = kq_ref.quantize_kv_ref(
        v_full.astype(jnp.bfloat16), bits, "tensor", block_n=block_n)
    return dict(q=q, kw=kw, k_scale=ksc, k_zero=kzp, vw=vw, v_scale=vsc,
                v_zero=vzp, k_res=k_res, v_res=v_res,
                pack_blocks=jnp.asarray(pack_blocks, jnp.int32),
                res_len=jnp.asarray(res_len, jnp.int32))


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
@pytest.mark.parametrize("num_splits", [2, 4])
def test_split_matches_unsplit_and_ref(bits, k_gran, num_splits):
    """num_splits in {2, 4} vs the unsplit kernel and the ref oracle.

    pack_blocks=[4, 2] with nb=4: at num_splits=4 the second sequence's
    upper splits own zero valid blocks, exercising the empty-split guard;
    res_len=[37, 0] covers a partially filled and an empty residual."""
    b, h, g, d, nb, block_n = 2, 2, 4, 128, 4, 128
    case = _make_case(
        jax.random.PRNGKey(0), b=b, h=h, g=g, d_k=d, d_v=d, nb=nb,
        block_n=block_n, bits=bits, k_gran=k_gran,
        pack_blocks=[nb, nb - 2], res_len=[37, 0],
    )
    args = paged_args(case, bits=bits, block_n=block_n, k_gran=k_gran)
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=bits,
                           block_n=block_n, k_gran=k_gran, return_lse=True)
    out_1, lse_1 = fn(*args, impl="pallas", num_splits=1)
    out_s, lse_s = fn(*args, impl="pallas", num_splits=num_splits)
    out_r, lse_r = fn(*args, impl="xla", num_splits=1)
    # split vs unsplit: same policy, only fp reassociation differs
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_1),
                               rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(lse_s), np.asarray(lse_1),
                               rtol=1e-3, atol=1e-3)
    # split vs the oracle
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(lse_s), np.asarray(lse_r),
                               rtol=1e-3, atol=1e-3)


def test_split_ref_oracle_matches_unsplit_ref():
    """The split-aware ref path (per-split partials + merge) is the oracle
    for the kernel's phase-2 merge: must agree with the single-pass ref."""
    case = _make_case(
        jax.random.PRNGKey(1), b=1, h=2, g=4, d_k=128, d_v=128, nb=6,
        block_n=128, bits=4, k_gran="channel", pack_blocks=[5], res_len=[19],
    )
    args = paged_args(case, bits=4, block_n=128)
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=4, block_n=128,
                           k_gran="channel", impl="xla", return_lse=True)
    out_1, lse_1 = fn(*args, num_splits=1)
    for s in (2, 3, 6):
        out_s, lse_s = fn(*args, num_splits=s)
        # bf16 PV matmuls run per split, so reassociation noise is the bound
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_1),
                                   rtol=1e-2, atol=1e-3)
        np.testing.assert_allclose(np.asarray(lse_s), np.asarray(lse_1),
                                   rtol=1e-4, atol=1e-4)


def test_split_all_splits_empty_but_residual():
    """pack_blocks=0: every split owns zero valid blocks; only the residual
    (owned by the last split) contributes.  Exercises lse=-inf partials for
    all non-last splits."""
    case = _make_case(
        jax.random.PRNGKey(2), b=1, h=1, g=4, d_k=128, d_v=128, nb=4,
        block_n=128, bits=4, k_gran="channel", pack_blocks=[0], res_len=[7],
    )
    args = paged_args(case, bits=4, block_n=128)
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=4, block_n=128,
                           k_gran="channel", return_lse=True)
    out_1, lse_1 = fn(*args, impl="pallas", num_splits=1)
    out_4, lse_4 = fn(*args, impl="pallas", num_splits=4)
    np.testing.assert_allclose(np.asarray(out_4), np.asarray(out_1),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(lse_4), np.asarray(lse_1),
                               rtol=1e-3, atol=1e-3)
    assert np.isfinite(np.asarray(out_4)).all()


def test_split_shared_kv_mla_mode():
    """MLA latent-cache split: V is a channel slice of dequantized K."""
    b, h, g, d_k, d_v, nb, block_n = 1, 1, 16, 256, 128, 4, 128
    case = _make_case(
        jax.random.PRNGKey(3), b=b, h=h, g=g, d_k=d_k, d_v=d_v, nb=nb,
        block_n=block_n, bits=4, k_gran="channel",
        pack_blocks=[3], res_len=[17],
    )
    case["vw"] = case["v_scale"] = case["v_zero"] = None
    case["v_res"] = None
    args = paged_args(case, bits=4, block_n=block_n, shared_kv=True)
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=4,
                           block_n=block_n, k_gran="channel", shared_kv=True,
                           d_v=d_v, return_lse=True)
    out_1, lse_1 = fn(*args, impl="pallas", num_splits=1)
    out_s, lse_s = fn(*args, impl="pallas", num_splits=2)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_1),
                               rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(lse_s), np.asarray(lse_1),
                               rtol=1e-3, atol=1e-3)


def test_paged_split_matches_unsplit():
    """Paged kernel: num_splits walks page-table ranges; parity with the
    unsplit paged kernel on a shuffled page table."""
    b, h, g, d, nb, block_n, bits = 2, 2, 4, 128, 4, 128, 4
    n_pages = b * nb + 3
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    k = jax.random.normal(ks[0], (1, h, n_pages * block_n, d), jnp.float32
                          ).astype(jnp.bfloat16)
    v = jax.random.normal(ks[1], (1, h, n_pages * block_n, d), jnp.float32
                          ).astype(jnp.bfloat16)
    kw, ksc, kzp = kq_ref.quantize_kv_ref(k, bits, "channel", block_n=block_n)
    vw, vsc, vzp = kq_ref.quantize_kv_ref(v, bits, "tensor", block_n=block_n)
    pool = lambda x: jnp.moveaxis(x[0], 1, 0)  # noqa: E731  [P, H, ...]
    table = jax.random.permutation(ks[5], n_pages)[: b * nb].reshape(b, nb)
    k_res = jax.random.normal(ks[3], (b, h, block_n, d), jnp.float32
                              ).astype(jnp.bfloat16)
    v_res = jax.random.normal(ks[4], (b, h, block_n, d), jnp.float32
                              ).astype(jnp.bfloat16)
    q = (jax.random.normal(ks[2], (b, h, g, d), jnp.float32) / d**0.25
         ).astype(jnp.bfloat16)
    args = (q, pool(kw), pool(ksc), pool(kzp), pool(vw), pool(vsc), pool(vzp),
            k_res, v_res, jnp.asarray(table, jnp.int32),
            jnp.asarray([nb, nb - 1], jnp.int32),
            jnp.asarray([21, 0], jnp.int32))
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=bits,
                           block_n=block_n, k_gran="channel", return_lse=True)
    out_1, lse_1 = fn(*args, impl="pallas", num_splits=1)
    out_s, lse_s = fn(*args, impl="pallas", num_splits=2)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_1),
                               rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(lse_s), np.asarray(lse_1),
                               rtol=1e-3, atol=1e-3)


def test_auto_heuristic_targets_small_batch_long_context():
    """auto splits exactly when B*H_kv underfills the cores and the packed
    sequence is long; each split must own >= 2 blocks."""
    assert pg_ops.auto_num_splits(8, 8, 64) == 1      # batch-heavy: never
    assert pg_ops.auto_num_splits(1, 2, 2) == 1       # too short
    s = pg_ops.auto_num_splits(1, 2, 64)              # B=1 GQA at 8K
    assert s > 1 and s * 2 <= 64
    assert pg_ops.auto_num_splits(1, 1, 6) <= 3       # >= 2 blocks per split
    assert pg_ops.resolve_num_splits("auto", 1, 2, 64) == s
    assert pg_ops.resolve_num_splits(3, 1, 2, 64) == 3
    assert pg_ops.resolve_num_splits(100, 1, 1, 4) == 4  # clamped to nb
