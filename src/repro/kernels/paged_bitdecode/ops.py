"""Public entry point for paged low-bit decode attention (Page setting).

``shared_kv=True`` is the MLA latent-cache mode: the pools hold a single
quantized latent stream (V-side pools and residual are ``None``), the kernel
reads each page once and slices the V tile out of the dequantized K tile.

Split-KV (FlashDecoding) dispatch lives here: ``num_splits`` partitions the
page-table columns into contiguous ranges, an extra parallel grid dimension;
grid step (row, split) walks its range in an in-kernel loop that stops at
the row's own ``pack_blocks`` (kernel.py phase 1), and the logsumexp merge
epilogue combines the splits (kernel.merge_partials, phase 2).
``num_splits="auto"`` applies the serving heuristic: split only when the
natural ``B x H_kv`` parallelism underfills the chip's cores AND the
sequence is long enough that each split still amortizes its setup over
>= 2 pages — i.e. exactly the paper's headline long-context small-batch
decode regime.

``layer`` makes the pools layer-stacked (``[L, P, H, ...]``, the decode
layer scan's layout) and names the layer the walk reads; without it the
pools are one layer's and are read as a stack of one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_bitdecode import kernel as _kernel
from repro.kernels.paged_bitdecode import ref as _ref

# Parallel grid slots the attached accelerators can fill concurrently.  TPU
# Mosaic maps "parallel" grid dims over Megacore (2 cores/chip); we target a
# little above that per device so splits also cover pipeline bubbles.
_CORES_PER_DEVICE = 4  # ~2 physical cores x2 oversubscription
_MAX_SPLITS = 16
_cores_cache: int | None = None


def default_splitkv_cores() -> int:
    """Parallel-slot target for the split heuristic: ``jax.device_count() *
    4``.  Resolved lazily (device_count initializes the backend) and cached
    for the process lifetime."""
    global _cores_cache
    if _cores_cache is None:
        _cores_cache = max(1, jax.device_count() * _CORES_PER_DEVICE)
    return _cores_cache


def _round_up(x, m):
    return -(-x // m) * m


def auto_num_splits(b: int, h_kv: int, nb: int, *, cores: int | None = None) -> int:
    """Split-KV heuristic: 1 unless B*H_kv underfills the cores and the
    packed sequence is long enough for every split to own >= 2 blocks."""
    cores = default_splitkv_cores() if cores is None else cores
    if b * h_kv >= cores or nb < 4:
        return 1
    want = -(-cores // (b * h_kv))  # splits needed to fill the cores
    return max(1, min(want, nb // 2, _MAX_SPLITS))


def resolve_num_splits(num_splits, b: int, h_kv: int, nb: int) -> int:
    if num_splits in (None, "auto"):
        return auto_num_splits(b, h_kv, nb)
    s = int(num_splits)
    if s < 1:
        raise ValueError(f"num_splits must be >= 1, got {num_splits}")
    return max(1, min(s, nb)) if nb else 1


def paged_bitdecode_attention(
    q,
    kw_pool, k_scale_pool, k_zero_pool,
    vw_pool, v_scale_pool, v_zero_pool,
    k_res, v_res,
    page_table, pack_blocks, res_len,
    *,
    layer=None,
    bits: int, block_n: int = 128, sm_scale: float | None = None,
    k_gran: str = "channel", shared_kv: bool = False, d_v: int | None = None,
    impl: str = "auto",
    num_splits: int | str | None = "auto", return_lse: bool = False,
    draft_bits: int | None = None,
):
    b, h, g, d_k = q.shape
    if layer is None:  # one layer's pools: a stack of one
        kw_pool, k_scale_pool, k_zero_pool, vw_pool, v_scale_pool, v_zero_pool = (
            None if p is None else p[None]
            for p in (kw_pool, k_scale_pool, k_zero_pool, vw_pool, v_scale_pool,
                      v_zero_pool))
        layer = 0
    layer = jnp.asarray(layer, jnp.int32)
    if shared_kv:
        if d_v is None:
            raise ValueError("shared_kv requires d_v")
    else:
        d_v = vw_pool.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / (d_k**0.5)
    if draft_bits is not None and draft_bits >= bits:
        draft_bits = None  # full-fidelity read: identical to the normal path
    if draft_bits is not None:
        if impl == "pallas":
            raise ValueError(
                "draft_bits (speculative draft read) has no Pallas kernel; "
                "use impl='xla' or 'auto'"
            )
        impl = "xla"
    elif impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if num_splits in (None, "auto") and impl == "xla":
        # splitting only pays on the Pallas grid: the XLA ref multiplies its
        # work by the split count (explicit integers are always honored)
        num_splits = 1
    else:
        num_splits = resolve_num_splits(num_splits, b, h, page_table.shape[1])
    if impl == "xla":
        out, lse = _ref.paged_bitdecode_attention_ref(
            q, kw_pool, k_scale_pool, k_zero_pool, vw_pool, v_scale_pool,
            v_zero_pool, k_res, v_res, page_table, pack_blocks, res_len, layer,
            bits=bits, block_n=block_n, sm_scale=sm_scale, k_gran=k_gran,
            shared_kv=shared_kv, d_v=d_v, num_splits=num_splits,
            draft_bits=draft_bits,
        )
        return (out, lse) if return_lse else out
    if impl != "pallas":
        raise ValueError(impl)

    g_p, dk_p = max(8, _round_up(g, 8)), _round_up(d_k, 128)

    def pad(x, axis_pads):
        cfg = [(0, 0)] * x.ndim
        for ax, p in axis_pads:
            cfg[ax] = (0, p)
        return jnp.pad(x, cfg) if any(p for _, p in axis_pads) else x

    q_p = pad(q, [(2, g_p - g), (3, dk_p - d_k)])
    kw_p = pad(kw_pool, [(4, dk_p - d_k)])
    if k_gran == "channel" and dk_p != d_k:
        ones = jnp.ones(k_scale_pool.shape[:-1] + (dk_p - d_k,), k_scale_pool.dtype)
        ks_p = jnp.concatenate([k_scale_pool, ones], axis=-1)
        kz_p = pad(k_zero_pool, [(3, dk_p - d_k)])
    else:
        ks_p, kz_p = k_scale_pool, k_zero_pool
    kres_p = pad(k_res, [(3, dk_p - d_k)])
    if shared_kv:
        # the V tile is a channel slice of the dequantized K tile; it must
        # stay a lane-aligned slice of the (padded) latent width
        if d_v % 128:
            raise ValueError(f"shared_kv requires d_v % 128 == 0, got {d_v}")
        vw_p = vs_p = vz_p = vres_p = None
        dv_eff = d_v
    else:
        dv_p = _round_up(d_v, 128)
        vw_p = pad(vw_pool, [(4, dv_p - d_v)])
        vs_p, vz_p = v_scale_pool, v_zero_pool
        vres_p = pad(v_res, [(3, dv_p - d_v)])
        dv_eff = dv_p

    o_parts, lse_parts = _kernel.paged_bitdecode_attention_pallas(
        q_p, kw_p, ks_p, kz_p, vw_p, vs_p, vz_p,
        kres_p, vres_p, page_table, pack_blocks, res_len, layer,
        bits=bits, block_n=block_n, sm_scale=float(sm_scale), k_gran=k_gran,
        shared_kv=shared_kv, d_v=dv_eff if shared_kv else None,
        num_splits=num_splits, interpret=jax.default_backend() != "tpu",
    )
    if o_parts.shape[0] == 1:  # unsplit: partials are already the answer
        out, lse = o_parts[0], lse_parts[0]
    else:
        out, lse = _kernel.merge_partials(o_parts, lse_parts)
    out = out[:, :, :g, :d_v]
    lse = lse[:, :, :g]
    return (out, lse) if return_lse else out
