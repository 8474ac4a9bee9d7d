"""The Pallas kernels compile for a TPU v5e at LLaMA-3.1-8B widths.

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses — block shapes that are no legal tile, unaligned slices, too much
VMEM.  These tests compile each kernel for a *described* v5e chip (the TPU
compiler runs here; nothing executes) and check that the program holds the
Mosaic kernel.  Widths: B=4, H_kv=8, g=4 (32 q heads), d=128, 128-token
blocks, a 512-page pool (8 slots x 8192 tokens), 4096-token prefill.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import layout
from repro.kernels.bitdecode import kernel as bd_kernel
from repro.kernels.flash_prefill import kernel as fp_kernel
from repro.kernels.kv_quant import kernel as kq_kernel
from repro.kernels.paged_bitdecode import kernel as pg_kernel
from repro.kernels.residual_flush import kernel as rf_kernel

B, H, G, D, BLOCK, PAGES, NB = 4, 8, 8, 128, 128, 520, 64  # G: g=4 padded to 8
I32, BF16 = jnp.int32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except (ImportError, RuntimeError, ValueError) as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)


def _compiles_to_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _param_width(k_gran):
    return D if k_gran == "channel" else BLOCK


@pytest.mark.parametrize("num_splits", [1, 4])
@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
@pytest.mark.parametrize("bits", [4, 2])
def test_paged_bitdecode(shape, bits, k_gran, num_splits):
    npr, p = layout.words_per_block(BLOCK, bits), _param_width(k_gran)
    args = [shape((B, H, G, D), BF16),
            shape((PAGES, H, npr, D), I32), shape((PAGES, H, p), BF16),
            shape((PAGES, H, p), BF16),
            shape((PAGES, H, npr, D), I32), shape((PAGES, H, BLOCK), BF16),
            shape((PAGES, H, BLOCK), BF16),
            shape((B, H, BLOCK, D), BF16), shape((B, H, BLOCK, D), BF16),
            shape((B, NB), I32), shape((B,), I32), shape((B,), I32)]
    _compiles_to_mosaic(
        lambda *a: pg_kernel.paged_bitdecode_attention_pallas(
            *a, bits=bits, block_n=BLOCK, sm_scale=D**-0.5, k_gran=k_gran,
            num_splits=num_splits, interpret=False),
        *args)


@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
@pytest.mark.parametrize("bits", [4, 2])
def test_paged_residual_flush(shape, bits, k_gran):
    npr, p = layout.words_per_block(BLOCK, bits), _param_width(k_gran)
    args = [shape((PAGES, H, npr, D), I32), shape((PAGES, H, p), BF16),
            shape((PAGES, H, p), BF16),
            shape((PAGES, H, npr, D), I32), shape((PAGES, H, BLOCK), BF16),
            shape((PAGES, H, BLOCK), BF16),
            shape((B, H, BLOCK, D), BF16), shape((B, H, BLOCK, D), BF16),
            shape((B,), I32), shape((B,), I32)]
    _compiles_to_mosaic(
        lambda *a: rf_kernel.paged_residual_flush_pallas(
            *a, bits=bits, block_n=BLOCK, k_gran=k_gran, interpret=False),
        *args)


@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
@pytest.mark.parametrize("bits", [4, 2])
def test_kv_quant(shape, bits, k_gran):
    _compiles_to_mosaic(
        lambda x: kq_kernel.quantize_kv_pallas(
            x, bits=bits, granularity=k_gran, block_n=BLOCK, interpret=False),
        shape((B, H, 4096, D), BF16))


@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
def test_dense_bitdecode(shape, k_gran):
    npr, p = layout.words_per_block(BLOCK, 4), _param_width(k_gran)
    args = [shape((B, H, G, D), BF16),
            shape((B, H, NB, npr, D), I32), shape((B, H, NB, p), BF16),
            shape((B, H, NB, p), BF16),
            shape((B, H, NB, npr, D), I32), shape((B, H, NB, BLOCK), BF16),
            shape((B, H, NB, BLOCK), BF16),
            shape((B, H, BLOCK, D), BF16), shape((B, H, BLOCK, D), BF16),
            shape((B,), I32), shape((B,), I32)]
    _compiles_to_mosaic(
        lambda *a: bd_kernel.bitdecode_attention_pallas(
            *a, bits=4, block_n=BLOCK, sm_scale=D**-0.5, k_gran=k_gran,
            shared_kv=False, d_v=D, num_splits=2, interpret=False),
        *args)


def test_dense_residual_flush(shape):
    npr = layout.words_per_block(BLOCK, 4)
    args = [shape((B, H, NB, npr, D), I32), shape((B, H, NB, D), BF16),
            shape((B, H, NB, D), BF16),
            shape((B, H, NB, npr, D), I32), shape((B, H, NB, BLOCK), BF16),
            shape((B, H, NB, BLOCK), BF16),
            shape((B, H, BLOCK, D), BF16), shape((B, H, BLOCK, D), BF16),
            shape((B,), I32), shape((B,), I32)]
    _compiles_to_mosaic(
        lambda *a: rf_kernel.residual_flush_pallas(
            *a, bits=4, block_n=BLOCK, k_gran="channel", shared_kv=False,
            interpret=False),
        *args)


@pytest.mark.parametrize("paged", [True, False])
def test_flush_auto_raises_on_misaligned_lanes(monkeypatch, paged):
    """On TPU ``auto`` picks the kernel and never falls back to the XLA
    reference: a head dim that is no lane multiple raises."""
    from repro.kernels.residual_flush import ops as rf_ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d, npr = 64, layout.words_per_block(BLOCK, 4)
    lead = (PAGES, H) if paged else (B, H, NB)
    sds = jax.ShapeDtypeStruct
    pools = [sds((*lead, npr, d), I32), sds((*lead, d), BF16), sds((*lead, d), BF16),
             sds((*lead, npr, d), I32), sds((*lead, BLOCK), BF16),
             sds((*lead, BLOCK), BF16)]
    res = [sds((B, H, BLOCK, d), BF16)] * 2
    flush = rf_ops.paged_residual_flush if paged else rf_ops.residual_flush
    with pytest.raises(ValueError, match="multiples of 128"):
        jax.eval_shape(
            lambda *a: flush(*a, bits=4, block_n=BLOCK, k_gran="channel",
                             shared_kv=False),
            *pools, *res, sds((B,), I32), sds((B,), I32))


def test_flash_prefill(shape):
    _compiles_to_mosaic(
        lambda q, k, v: fp_kernel.flash_prefill_pallas(
            q, k, v, bq=256, bk=256, sm_scale=D**-0.5, causal=True,
            s_valid=4096, interpret=False),
        shape((1, 4 * H, 4096, D), BF16), shape((1, H, 4096, D), BF16),
        shape((1, H, 4096, D), BF16))
