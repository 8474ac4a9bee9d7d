"""The Pallas kernels compile for a TPU v5e at LLaMA-3.1-8B widths.

Interpret mode (every other kernel test) cannot see what the chip's compiler
refuses — block shapes that are no legal tile, unaligned slices, too much
VMEM.  These tests compile each kernel for a *described* v5e chip (the TPU
compiler runs here; nothing executes) and check that the program holds the
Mosaic kernel.  Widths: B=4, H_kv=8, g=4 (32 q heads), d=128, 128-token
blocks, a 512-page pool (8 slots x 8192 tokens) in each of 2 layers,
4096-token prefill; and the benchmark cells' own shapes below.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import layout, qcache
from repro.kernels.flash_prefill import kernel as fp_kernel
from repro.kernels.kv_quant import kernel as kq_kernel
from repro.kernels.paged_bitdecode import kernel as pg_kernel
from repro.kernels.residual_flush import kernel as rf_kernel

B, H, G, D, BLOCK, PAGES, NB = 4, 8, 8, 128, 128, 520, 64  # G: g=4 padded to 8
L = 2  # layers of the stacked pools
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


def _pools(shape, *, layers, pages, h, d, bits=4, k_gran="channel", v=True):
    """Layer-stacked page pools: K words, scale, zero (and the V side)."""
    npr, p = layout.words_per_block(BLOCK, bits), d if k_gran == "channel" else BLOCK
    lead = (layers, pages, h)
    out = [shape((*lead, npr, d), I32), shape((*lead, p), BF16), shape((*lead, p), BF16)]
    if v:
        out += [shape((*lead, npr, d), I32), shape((*lead, BLOCK), BF16),
                shape((*lead, BLOCK), BF16)]
    return out


def _hbm_copies(text, prefix):
    """Copies into HBM of arrays whose type starts with ``prefix`` in a
    compiled program's text (``copy``, and the destination of
    ``copy-start``); moves into VMEM (``S(1)``) are the compiler's
    prefetches, not copies of a buffer."""
    out = []
    for line in text.splitlines():
        for op in (" copy(", " copy-start("):
            if op in line:
                dest = line.split(" = ", 1)[1].split(op)[0].lstrip("(")
                if dest.startswith(prefix) and "S(1)" not in dest.split("}, ")[0]:
                    out.append(line.strip()[:160])
    return out


def _compiles_aliased(fn, pools, *rest):
    """``fn`` compiles to a Mosaic kernel that writes the donated ``pools``
    in place: all their bytes alias, and the packed-word pools (the bulk of
    them) are never copied.  (A standalone program may still relayout a
    one-head pool of params between its parameter's layout and the
    kernel's; the decode step does that once, outside its layer loop.)"""
    donate = tuple(range(len(pools)))
    compiled = jax.jit(fn, donate_argnums=donate).lower(*pools, *rest).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    pool_bytes = sum(p.size * p.dtype.itemsize for p in pools)
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes
    words = pools[0].shape
    assert not _hbm_copies(text, f"s32[{words[0]},{words[1]},")


@pytest.mark.parametrize("num_splits", [1, 4])
@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
@pytest.mark.parametrize("bits", [4, 2])
def test_paged_bitdecode(shape, bits, k_gran, num_splits):
    args = [shape((B, H, G, D), BF16),
            *_pools(shape, layers=L, pages=PAGES, h=H, d=D, bits=bits, k_gran=k_gran),
            shape((B, H, BLOCK, D), BF16), shape((B, H, BLOCK, D), BF16),
            shape((B, NB), I32), shape((B,), I32), shape((B,), I32), shape((), I32)]
    _compiles_to_mosaic(
        lambda *a: pg_kernel.paged_bitdecode_attention_pallas(
            *a, bits=bits, block_n=BLOCK, sm_scale=D**-0.5, k_gran=k_gran,
            num_splits=num_splits, interpret=False),
        *args)


@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
@pytest.mark.parametrize("bits", [4, 2])
def test_paged_residual_flush(shape, bits, k_gran):
    _compiles_aliased(
        lambda *a: rf_kernel.paged_residual_flush_pallas(
            *a, bits=bits, block_n=BLOCK, k_gran=k_gran, interpret=False),
        _pools(shape, layers=L, pages=PAGES, h=H, d=D, bits=bits, k_gran=k_gran),
        shape((B, H, BLOCK, D), BF16), shape((B, H, BLOCK, D), BF16),
        shape((B,), I32), shape((B,), I32), shape((), I32))


# Qwen2-7B at the qwen2-7b.reason cell's shapes: 4 KV heads, g=7 padded to
# 8, d=128; 32 slots, a 1,120-page pool (32 x 34 pages + 32 scratch) in each
# of the stage's 14 layers, 4-bit, 128-token blocks
QB, QH, QG, QP, QNB, QL = 32, 4, 8, 1120, 34, 14


def test_qwen_stacked_paged_bitdecode(shape):
    _compiles_to_mosaic(
        lambda *a: pg_kernel.paged_bitdecode_attention_pallas(
            *a, bits=4, block_n=BLOCK, sm_scale=D**-0.5, k_gran="channel",
            num_splits=1, interpret=False),
        shape((QB, QH, QG, D), BF16), *_pools(shape, layers=QL, pages=QP, h=QH, d=D),
        shape((QB, QH, BLOCK, D), BF16), shape((QB, QH, BLOCK, D), BF16),
        shape((QB, QNB), I32), shape((QB,), I32), shape((QB,), I32), shape((), I32))


def test_qwen_stacked_paged_residual_flush(shape):
    _compiles_aliased(
        lambda *a: rf_kernel.paged_residual_flush_pallas(
            *a, bits=4, block_n=BLOCK, k_gran="channel", interpret=False),
        _pools(shape, layers=QL, pages=QP, h=QH, d=D),
        shape((QB, QH, BLOCK, D), BF16), shape((QB, QH, BLOCK, D), BF16),
        shape((QB,), I32), shape((QB,), I32), shape((), I32))


@pytest.mark.parametrize("op", ["paged_bitdecode", "paged_residual_flush", "kv_quant"])
def test_qwen_cell_ops(shape, monkeypatch, op):
    """The ops the qwen2-7b.reason cell's programs call, as the decode step
    and the prefill call them on the chip (``impl="auto"``, which picks the
    kernel on a TPU): the 4-bit channel walk over the stacked pools with the
    cell's g=7 query heads per KV head (h=4; the op pads g to 8), the flush
    at a layer offset, and the prefill's quantization of 32 slots x the
    cell's longest bucket (256)."""
    from repro.kernels.kv_quant import ops as kq_ops
    from repro.kernels.paged_bitdecode import ops as pg_ops
    from repro.kernels.residual_flush import ops as rf_ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pools = _pools(shape, layers=QL, pages=QP, h=QH, d=D)
    res = [shape((QB, QH, BLOCK, D), BF16)] * 2
    if op == "paged_bitdecode":
        _compiles_to_mosaic(
            lambda q, *a: pg_ops.paged_bitdecode_attention(
                q, *a[:-1], layer=a[-1], bits=4, block_n=BLOCK, k_gran="channel"),
            shape((QB, QH, 7, D), BF16), *pools, *res, shape((QB, QNB), I32),
            shape((QB,), I32), shape((QB,), I32), shape((), I32))
    elif op == "paged_residual_flush":
        _compiles_aliased(
            lambda *a: rf_ops.paged_residual_flush(
                *a[:-1], layer=a[-1], bits=4, block_n=BLOCK, k_gran="channel"),
            pools, *res, shape((QB,), I32), shape((QB,), I32), shape((), I32))
    else:
        _compiles_to_mosaic(
            lambda x: kq_ops.quantize_kv(x, 4, "channel", block_n=BLOCK),
            shape((QB, QH, 256, D), BF16))


@pytest.mark.parametrize("k_gran", ["channel", "tensor"])
@pytest.mark.parametrize("bits", [4, 2])
def test_kv_quant(shape, bits, k_gran):
    _compiles_to_mosaic(
        lambda x: kq_kernel.quantize_kv_pallas(
            x, bits=bits, granularity=k_gran, block_n=BLOCK, interpret=False),
        shape((B, H, 4096, D), BF16))


def test_flush_auto_raises_on_misaligned_lanes(monkeypatch):
    """On TPU ``auto`` picks the kernel and never falls back to the XLA
    reference: a head dim that is no lane multiple raises."""
    from repro.kernels.residual_flush import ops as rf_ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    d, npr = 64, layout.words_per_block(BLOCK, 4)
    lead = (PAGES, H)
    sds = jax.ShapeDtypeStruct
    pools = [sds((*lead, npr, d), I32), sds((*lead, d), BF16), sds((*lead, d), BF16),
             sds((*lead, npr, d), I32), sds((*lead, BLOCK), BF16),
             sds((*lead, BLOCK), BF16)]
    res = [sds((B, H, BLOCK, d), BF16)] * 2
    with pytest.raises(ValueError, match="multiples of 128"):
        jax.eval_shape(
            lambda *a: rf_ops.paged_residual_flush(
                *a, bits=4, block_n=BLOCK, k_gran="channel", shared_kv=False),
            *pools, *res, sds((B,), I32), sds((B,), I32))


def test_flash_prefill(shape):
    _compiles_to_mosaic(
        lambda q, k, v: fp_kernel.flash_prefill_pallas(
            q, k, v, bq=256, bk=256, sm_scale=D**-0.5, causal=True,
            s_valid=4096, interpret=False),
        shape((1, 4 * H, 4096, D), BF16), shape((1, H, 4096, D), BF16),
        shape((1, H, 4096, D), BF16))


# DeepSeek-V3's latent path at the deepseek-v3.reason cell's shapes: one
# latent stream (h=1) of 576 channels, stored lane-aligned in 640, feeding
# g=128 query heads and a 512-channel value slice; 32 slots, a 1,120-page
# pool (32 x 34 pages + 32 scratch) in each of the 7 MoE layers, 4-bit,
# 128-token blocks
LB, LP, LNB, LD, LDV, LG, LL = 32, 1120, 34, 640, 512, 128, 7


def _latent_pools(shape, d=LD):
    return _pools(shape, layers=LL, pages=LP, h=1, d=d, v=False)


def _latent_paged_bitdecode(shape, num_splits):
    args = [shape((LB, 1, LG, LD), BF16), *_latent_pools(shape),
            shape((LB, 1, BLOCK, LD), BF16), shape((LB, LNB), I32), shape((LB,), I32),
            shape((LB,), I32), shape((), I32)]
    _compiles_to_mosaic(
        lambda q, kw, ks, kz, kr, pt, pb, rl, ly: pg_kernel.paged_bitdecode_attention_pallas(
            q, kw, ks, kz, None, None, None, kr, None, pt, pb, rl, ly, bits=4,
            block_n=BLOCK, sm_scale=0.1, k_gran="channel", shared_kv=True, d_v=LDV,
            num_splits=num_splits, interpret=False),
        *args)


def test_latent_paged_bitdecode(shape):
    _latent_paged_bitdecode(shape, 1)


def test_latent_paged_bitdecode_split(shape):
    """The latent walk split over 4 column ranges of each row."""
    _latent_paged_bitdecode(shape, 4)


def test_latent_paged_residual_flush(shape):
    _compiles_aliased(
        lambda kw, ks, kz, kr, full, dest, ly: rf_kernel.paged_residual_flush_pallas(
            kw, ks, kz, None, None, None, kr, None, full, dest, ly, bits=4,
            block_n=BLOCK, k_gran="channel", shared_kv=True, interpret=False)[:3],
        _latent_pools(shape), shape((LB, 1, BLOCK, LD), BF16), shape((LB,), I32),
        shape((LB,), I32), shape((), I32))


def test_latent_kv_quant(shape):
    # the prefill of 32 slots x the cell's longest prompt bucket (256)
    _compiles_to_mosaic(
        lambda x: kq_kernel.quantize_kv_pallas(
            x, bits=4, granularity="channel", block_n=BLOCK, interpret=False),
        shape((LB, 1, 256, LD), BF16))


@pytest.mark.parametrize("m,k,n", [(256, 7168, 4096), (256, 2048, 7168),
                                   (16384, 7168, 4096), (16384, 2048, 7168)],
                         ids=["decode_up", "decode_down", "prefill_up", "prefill_down"])
def test_held_expert_grouped_matmul(shape, m, k, n):
    """The share's grouped matmul over 8 held experts (plus the trailing
    group of pairs held elsewhere), at DeepSeek-V3's widths: a decode step's
    32 x 8 pairs and a prefill chunk's 2,048 x 8."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from repro.models import moe

    _compiles_to_mosaic(
        lambda lhs, rhs, sizes: gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32,
                                    tiling=moe.gmm_tiling(m, k, n)),
        shape((m, k), BF16), shape((8, k, n), BF16), shape((9,), I32))


@pytest.mark.parametrize("width,copies", [(LD, False), (576, True)],
                         ids=["lane_aligned", "unaligned"])
def test_latent_walk_copies_no_pool(width, copies):
    """At the pool's lane-aligned width the decode walk hands the pools to
    the kernel as they are; at 576 channels it would pad (copy) them on
    every call."""
    from repro.kernels.paged_bitdecode import ops as pg_ops

    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(
        lambda q, kw, ks, kz, kr, pt, pb, rl: pg_ops.paged_bitdecode_attention(
            q, kw, ks, kz, None, None, None, kr, None, pt, pb, rl, bits=4,
            block_n=BLOCK, sm_scale=0.1, k_gran="channel", shared_kv=True, d_v=LDV,
            impl="pallas", num_splits=1, layer=3))(
        sds((LB, 1, LG, width), BF16), *_latent_pools(sds, width),
        sds((LB, 1, BLOCK, width), BF16), sds((LB, LNB), I32), sds((LB,), I32),
        sds((LB,), I32))
    pool_sized = [e for e in jaxpr.jaxpr.eqns if e.primitive.name != "pallas_call"
                  and any(LP in getattr(v.aval, "shape", ()) for v in e.outvars)]
    assert bool(pool_sized) == copies, pool_sized


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (scan and
    cond bodies), but not inside a ``pallas_call``."""
    from jax.extend import core as jc

    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for val in e.params.values():
            for w in val if isinstance(val, (tuple, list)) else (val,):
                if isinstance(w, jc.ClosedJaxpr):
                    yield from _eqns(w.jaxpr)
                elif isinstance(w, jc.Jaxpr):
                    yield from _eqns(w)


@pytest.mark.parametrize("arch", ["llama3-8b", "deepseek-v3-671b"], ids=["attn", "mla"])
def test_decode_step_copies_no_layer_pool(arch):
    """The decode layer scan hands the kernels the layer-stacked pools and a
    layer index: outside a Pallas kernel, no equation of the decode step
    (the scan bodies and the flush gate included) makes an array of one
    layer's pool shape, which a slice of the stack or a write-back would."""
    from repro.configs.base import smoke_config
    from repro.models.zoo import build_model

    cfg = smoke_config(arch).with_(kv_bits=4, kv_block=32)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: model.init_paged_decode_state(2, n_pages=11, nb_max=3))
    layer_pools = {p.shape[1:] for p in qcache.split_pools(state["caches"][-1])[0]
                   if p is not None}
    assert all(s[0] == 11 for s in layer_pools)
    jaxpr = jax.make_jaxpr(lambda p, s, t: model.decode_step(
        p, s, t, impl="pallas", quant_impl="pallas"))(
        params, state, jax.ShapeDtypeStruct((2, 1), I32))
    made = [(e.primitive.name, v.aval.shape) for e in _eqns(jaxpr.jaxpr)
            if e.primitive.name != "pallas_call" for v in e.outvars
            if getattr(v.aval, "shape", None) in layer_pools]
    assert not made, made
