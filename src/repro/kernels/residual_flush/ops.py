"""Public entry point for the fused residual-flush (quantize+pack+commit)."""
from __future__ import annotations

import jax

from repro.kernels.residual_flush import kernel as _kernel
from repro.kernels.residual_flush import ref as _ref


def residual_flush(
    kw,
    k_scale,
    k_zero,
    vw,
    v_scale,
    v_zero,
    k_res,
    v_res,
    full,
    dest_block,
    *,
    bits: int,
    block_n: int,
    k_gran: str,
    shared_kv: bool,
    impl: str = "auto",
):
    """Commit the bf16 residual of every sequence with ``full[b] != 0`` into
    packed block ``dest_block[b]`` of the low-bit cache.

    Arguments mirror the QuantKVCache packed/residual fields (V side None
    when ``shared_kv``); returns the six updated packed arrays.  Callers gate
    the invocation on ``jnp.any(full)`` (see ``qcache.append_decode``) so the
    per-token hot path performs no quantization work at all.

    impl: 'pallas' (single fused kernel, in-place via aliasing; interpret
    mode off-TPU), 'xla' (the select-based reference oracle), or 'auto'
    (pallas on TPU, xla elsewhere).  The aliased cache cannot be lane-padded
    in place, so on TPU a head dim that is not lane-aligned raises; pass
    ``impl='xla'`` to run such a shape through the reference.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        interpret = jax.default_backend() != "tpu"
        return _kernel.residual_flush_pallas(
            kw, k_scale, k_zero, vw, v_scale, v_zero, k_res, v_res,
            full, dest_block,
            bits=bits, block_n=block_n, k_gran=k_gran, shared_kv=shared_kv,
            interpret=interpret,
        )
    if impl == "xla":
        return _ref.residual_flush_ref(
            kw, k_scale, k_zero, vw, v_scale, v_zero, k_res, v_res,
            full, dest_block,
            bits=bits, block_n=block_n, k_gran=k_gran, shared_kv=shared_kv,
        )
    raise ValueError(f"unknown impl {impl!r}")


def paged_residual_flush(
    kw_pool,
    k_scale_pool,
    k_zero_pool,
    vw_pool,
    v_scale_pool,
    v_zero_pool,
    k_res,
    v_res,
    full,
    dest_page,
    *,
    bits: int,
    block_n: int,
    k_gran: str,
    shared_kv: bool = False,
    impl: str = "auto",
):
    """Paged face of the fused residual flush: commit the bf16 residual of
    every sequence with ``full[b] != 0`` into pool page ``dest_page[b]`` of
    the shared ``[P, H, ...]`` page pools.

    Same gating contract as :func:`residual_flush` (callers wrap the call in
    ``lax.cond(any(full))`` — see ``qcache.paged_append_decode``), plus the
    paged injectivity contract: ``dest_page`` entries must be pairwise
    distinct.  Callers satisfy it by pointing non-flushing sequences at their
    reserved per-slot scratch page (pool pages ``[0, B)``, never allocated to
    requests — serve/pages.py).  ``shared_kv`` is the MLA latent-pool mode
    (no V-side pools; V operands are ``None``).

    impl: 'pallas' | 'xla' | 'auto' (pallas on TPU, xla elsewhere; a pool
    minor dim that is not lane-aligned raises on TPU, exactly like the dense
    flush).
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        return _kernel.paged_residual_flush_pallas(
            kw_pool, k_scale_pool, k_zero_pool, vw_pool, v_scale_pool,
            v_zero_pool, k_res, v_res, full, dest_page,
            bits=bits, block_n=block_n, k_gran=k_gran, shared_kv=shared_kv,
            interpret=jax.default_backend() != "tpu",
        )
    if impl == "xla":
        return _ref.paged_residual_flush_ref(
            kw_pool, k_scale_pool, k_zero_pool, vw_pool, v_scale_pool,
            v_zero_pool, k_res, v_res, full, dest_page,
            bits=bits, block_n=block_n, k_gran=k_gran, shared_kv=shared_kv,
        )
    raise ValueError(f"unknown impl {impl!r}")
