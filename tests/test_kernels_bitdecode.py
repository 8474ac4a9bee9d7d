"""Per-kernel allclose tests: the paged_bitdecode Pallas kernel vs the
pure-jnp oracle over a dense cache's blocks under an identity page table
(``qcache.to_pages``), plus fidelity vs the exact fp16 attention (paper
Table I analogue)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qcache
from repro.kernels.kv_quant import ref as kq_ref
from repro.kernels.paged_bitdecode import ops as pg_ops


def paged_args(case, *, bits, block_n, k_gran="channel", shared_kv=False):
    """A case's dense blocks (``kw [B, H, nb, ...]`` etc.) as the positional
    arguments of ``paged_bitdecode_attention``: pools and page table from
    ``qcache.to_pages``, ``q`` first."""
    pages = qcache.to_pages(qcache.QuantKVCache(
        **{f: case[f] for f in ("kw", "k_scale", "k_zero", "vw", "v_scale",
                                "v_zero", "k_res", "v_res", "pack_blocks",
                                "res_len")},
        bits=bits, block_n=block_n, k_gran=k_gran, shared_kv=shared_kv))
    return (case["q"], pages.kw, pages.k_scale, pages.k_zero, pages.vw,
            pages.v_scale, pages.v_zero, pages.k_res, pages.v_res,
            pages.page_table, pages.pack_blocks, pages.res_len)


def _make_case(key, *, b, h, g, d_k, d_v, nb, block_n, res_n, bits, k_gran,
               pack_blocks, res_len):
    ks = jax.random.split(key, 6)
    s_pack = nb * block_n
    k_full = jax.random.normal(ks[0], (b, h, s_pack, d_k), jnp.float32)
    k_full += 3.0 * jax.random.normal(ks[5], (d_k,), jnp.float32)  # outlier channels
    v_full = jax.random.normal(ks[1], (b, h, s_pack, d_v), jnp.float32)
    q = (jax.random.normal(ks[2], (b, h, g, d_k), jnp.float32) / d_k**0.25).astype(jnp.bfloat16)
    k_res = jax.random.normal(ks[3], (b, h, res_n, d_k), jnp.float32).astype(jnp.bfloat16)
    v_res = jax.random.normal(ks[4], (b, h, res_n, d_v), jnp.float32).astype(jnp.bfloat16)

    kw, ksc, kzp = kq_ref.quantize_kv_ref(k_full.astype(jnp.bfloat16), bits, k_gran, block_n=block_n)
    vw, vsc, vzp = kq_ref.quantize_kv_ref(v_full.astype(jnp.bfloat16), bits, "tensor", block_n=block_n)
    pb = jnp.asarray(pack_blocks, jnp.int32)
    rl = jnp.asarray(res_len, jnp.int32)
    return dict(q=q, kw=kw, k_scale=ksc, k_zero=kzp, vw=vw, v_scale=vsc,
                v_zero=vzp, k_res=k_res, v_res=v_res, pack_blocks=pb, res_len=rl), \
           (k_full, v_full)


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
@pytest.mark.parametrize("g", [1, 4, 16])
@pytest.mark.parametrize("d", [128, 256])
def test_bitdecode_pallas_matches_ref(bits, k_gran, g, d):
    b, h, nb, block_n = 2, 2, 3, 128
    case, _ = _make_case(
        jax.random.PRNGKey(0), b=b, h=h, g=g, d_k=d, d_v=d, nb=nb,
        block_n=block_n, res_n=block_n, bits=bits, k_gran=k_gran,
        pack_blocks=[nb, nb - 1], res_len=[37, 0],
    )
    args = paged_args(case, bits=bits, block_n=block_n, k_gran=k_gran)
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=bits,
                           block_n=block_n, k_gran=k_gran, return_lse=True)
    out_p, lse_p = fn(*args, impl="pallas")
    out_r, lse_r = fn(*args, impl="xla")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(lse_p), np.asarray(lse_r), rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("g,d", [(3, 64), (7, 192)])
def test_bitdecode_unaligned_shapes(g, d):
    """Padding path: g not multiple of 8, d not multiple of 128."""
    bits, k_gran, b, h, nb, block_n = 4, "channel", 1, 2, 2, 128
    case, _ = _make_case(
        jax.random.PRNGKey(1), b=b, h=h, g=g, d_k=d, d_v=d, nb=nb,
        block_n=block_n, res_n=block_n, bits=bits, k_gran=k_gran,
        pack_blocks=[nb], res_len=[5],
    )
    args = paged_args(case, bits=bits, block_n=block_n, k_gran=k_gran)
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=bits,
                           block_n=block_n, k_gran=k_gran, return_lse=True)
    out_p, lse_p = fn(*args, impl="pallas")
    out_r, lse_r = fn(*args, impl="xla")
    assert out_p.shape == (b, h, g, d)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(lse_p), np.asarray(lse_r), rtol=1e-3, atol=1e-3)


def test_bitdecode_shared_kv_mla_mode():
    """MLA latent-cache mode: V = first d_v channels of the dequantized K."""
    bits, b, h, g, d_k, d_v, nb, block_n = 4, 1, 1, 16, 256, 128, 2, 128
    case, _ = _make_case(
        jax.random.PRNGKey(2), b=b, h=h, g=g, d_k=d_k, d_v=d_v, nb=nb,
        block_n=block_n, res_n=block_n, bits=bits, k_gran="channel",
        pack_blocks=[nb], res_len=[17],
    )
    case = dict(case)
    case["vw"] = case["v_scale"] = case["v_zero"] = None
    case["v_res"] = None
    args = paged_args(case, bits=bits, block_n=block_n, shared_kv=True)
    fn = functools.partial(pg_ops.paged_bitdecode_attention, bits=bits,
                           block_n=block_n, k_gran="channel", shared_kv=True,
                           d_v=d_v, return_lse=True)
    out_p, lse_p = fn(*args, impl="pallas")
    out_r, lse_r = fn(*args, impl="xla")  # residual V: the slice of residual K
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("bits,max_err", [(8, 0.02), (4, 0.12), (2, 0.50)])
def test_bitdecode_fidelity_vs_fp16(bits, max_err):
    """Quantized attention tracks exact fp16 attention (Table I analogue).

    Thresholds are calibrated for iid-Gaussian K/V with outlier channels —
    near worst-case for quantization (real LLM keys are low-rank/structured
    and quantize far better, cf. KIVI).  The benchmark suite reports the
    measured fidelity curve; here we pin sane magnitudes and the 8<4<2-bit
    error ordering.
    """
    b, h, g, d, nb, block_n = 1, 4, 4, 128, 4, 128
    case, (k_full, v_full) = _make_case(
        jax.random.PRNGKey(3), b=b, h=h, g=g, d_k=d, d_v=d, nb=nb,
        block_n=block_n, res_n=block_n, bits=bits, k_gran="channel",
        pack_blocks=[nb], res_len=[64],
    )
    out_q = pg_ops.paged_bitdecode_attention(
        *paged_args(case, bits=bits, block_n=block_n), bits=bits,
        block_n=block_n, k_gran="channel", impl="xla")
    # exact fp16 oracle over the same tokens
    k_all = jnp.concatenate([k_full, case["k_res"][:, :, :64].astype(jnp.float32)], axis=2)
    v_all = jnp.concatenate([v_full, case["v_res"][:, :, :64].astype(jnp.float32)], axis=2)
    s = jnp.einsum("bhgd,bhtd->bhgt", case["q"].astype(jnp.float32), k_all) / d**0.5
    p = jax.nn.softmax(s, axis=-1)
    out_f = jnp.einsum("bhgt,bhtd->bhgd", p, v_all)
    rel = np.linalg.norm(np.asarray(out_q) - np.asarray(out_f)) / np.linalg.norm(np.asarray(out_f))
    assert rel < max_err, f"relative error {rel:.4f} exceeds {max_err}"
