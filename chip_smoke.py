"""Smoke run of the serving path on a TPU: the quickest proof that the system
still starts on the chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the cross-chip path only, four chips

One process; every phase raises on failure, so any failure exits non-zero.
With no TPU attached it exits non-zero before any phase and prints no result.

One chip, in order:
  a. the device line;
  b. on-chip parity of the serving kernels at LLaMA-3.1-8B widths
     (``paged_bitdecode``, the paged ``residual_flush``, ``kv_quant``): Pallas
     against the XLA references, at the tolerances of the interpret tests;
  c. serving: LLaMA-3.1-8B at its published widths with 16 of its 32 layers
     (the bf16 weights of all 32 do not fit one 16 GB chip), 4-bit cache,
     128-token blocks, channel-wise K, seeded random weights.  Eight requests
     over eight slots (``max_seq`` 8192), prompts of 520-3900 tokens, one of
     them sharing a 1024-token prefix, 32-64 new tokens each.  Prints the Mosaic kernels in the
     compiled decode and prefill steps and, for information only, compile
     seconds, tokens/s, TTFT p50 and peak device memory;
  d. the same model's prefill and first decode steps through the XLA
     references (``impl="xla", quant_impl="xla"``): the logits must agree
     with the Pallas path.

Four chips (``--chips 4``): the same model on a ``(4,)`` "data" mesh, decoding
through the cross-chip split-KV walk with page-affine pools, compared token
for token with the walk over replicated pools; prints the per-chip pool bytes.

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# ---- tolerances (the interpret-mode tests' where a kernel has one) -------
DECODE_TOL = dict(out=2e-2, lse=1e-3)   # tests/test_kernels_paged.py
KVQ_PARAM_TOL = dict(rtol=1e-2, atol=1e-3)  # tests/test_kernels_kvquant.py
# logits of the whole model, Pallas vs XLA path, as ||diff||_2 / ||xla||_2.
# Both paths read the same bf16 weights and bitwise-equal 4-bit caches and
# differ only in where the softmax rounds to bf16.  On a v5e at 16 layers
# that floor is 1.4e-2 (the XLA path against itself with four in-kernel
# splits reads the same); caches quantized from values one bf16 rounding
# apart read 6.5e-2, a wrong page, head or scale O(1).
LOGIT_REL_TOL = 3e-2

B_SLOTS, MAX_SEQ, SERVE_LAYERS = 8, 8192, 16


def log(*a):
    print(*a, flush=True)


def serving_config(n_layers: int = SERVE_LAYERS):
    from repro.configs.base import get_config

    return get_config("llama3-8b").with_(
        n_layers=n_layers, kv_bits=4, kv_block=128, kv_gran="channel")


def mosaic_kernels(compiled) -> dict:
    """Mosaic custom calls in a compiled program, counted by kernel name."""
    return dict(collections.Counter(re.findall(
        r'%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"',
        compiled.as_text())))


def _require(ok: bool, what: str):
    if not ok:
        raise AssertionError(what)


# ------------------------------------------------------------------ phase b


def kernel_parity(seed: int, *, h=8, g=4, d=128, block_n=128, b=8, nb=16):
    """Pallas vs XLA reference for the serving kernels, on the device."""
    from repro.kernels.kv_quant import ops as kvq
    from repro.kernels.paged_bitdecode import ops as pgd
    from repro.kernels.residual_flush import ops as rfl

    n_pages = b + b * nb
    key = jax.random.PRNGKey(seed)
    failures = []

    def pools(bits, k_gran, key):
        k1, k2 = jax.random.split(key)
        shape = (1, h, n_pages * block_n, d)
        k = jax.random.normal(k1, shape, jnp.float32).astype(jnp.bfloat16)
        v = jax.random.normal(k2, shape, jnp.float32).astype(jnp.bfloat16)
        kw, ks, kz = kvq.quantize_kv(k, bits, k_gran, block_n=block_n, impl="xla")
        vw, vs, vz = kvq.quantize_kv(v, bits, "tensor", block_n=block_n, impl="xla")
        # [1, H, P, ...] -> pools [P, H, ...]
        return [jnp.moveaxis(x[0], 1, 0) for x in (kw, ks, kz, vw, vs, vz)]

    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (b, h, g, d), jnp.float32).astype(jnp.bfloat16)
    k_res = jax.random.normal(ks[1], (b, h, block_n, d), jnp.float32).astype(jnp.bfloat16)
    v_res = jax.random.normal(ks[2], (b, h, block_n, d), jnp.float32).astype(jnp.bfloat16)
    perm = np.asarray(jax.random.permutation(ks[3], np.arange(b, n_pages)))
    table = jnp.asarray(perm[: b * nb].reshape(b, nb), jnp.int32)
    pack = jnp.asarray(np.linspace(nb, 0, b).astype(np.int32))
    res = jnp.asarray(np.arange(b) * 15 % block_n, jnp.int32)

    for bits, k_gran, splits in ((4, "channel", 1), (4, "channel", 4), (2, "tensor", 1)):
        pool = pools(bits, k_gran, ks[4])
        args = (q, *pool, k_res, v_res, table, pack, res)
        kw = dict(bits=bits, block_n=block_n, k_gran=k_gran, num_splits=splits,
                  return_lse=True)
        o_p, l_p = pgd.paged_bitdecode_attention(*args, impl="pallas", **kw)
        o_r, l_r = pgd.paged_bitdecode_attention(*args, impl="xla", **kw)
        o_p, l_p, o_r, l_r = map(np.asarray, (o_p, l_p, o_r, l_r))
        eo, el = np.abs(o_p - o_r).max(), np.abs(l_p - l_r).max()
        ok = (np.allclose(o_p, o_r, rtol=DECODE_TOL["out"], atol=DECODE_TOL["out"])
              and np.allclose(l_p, l_r, rtol=DECODE_TOL["lse"], atol=DECODE_TOL["lse"]))
        log(f"[parity] paged_bitdecode bits={bits} k_gran={k_gran} splits={splits}: "
            f"max|dout|={eo:.3e} max|dlse|={el:.3e} {'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(f"paged_bitdecode bits={bits} {k_gran} splits={splits}")

    # paged flush: a mixed batch; non-flushing rows target their scratch page
    pool = pools(4, "channel", ks[5])
    full = jnp.asarray([1, 0, 1, 1, 0, 1, 0, 1], jnp.int32)[:b]
    dest = jnp.where(full != 0, table[:, 0], jnp.arange(b, dtype=jnp.int32))
    fargs = (*pool, k_res, v_res, full, dest)
    fkw = dict(bits=4, block_n=block_n, k_gran="channel")
    out_p = rfl.paged_residual_flush(*fargs, impl="pallas", **fkw)
    out_r = rfl.paged_residual_flush(*fargs, impl="xla", **fkw)
    for name, xp, xr in zip(("kw", "k_scale", "k_zero", "vw", "v_scale", "v_zero"),
                            out_p, out_r):
        xp, xr = np.asarray(xp), np.asarray(xr)
        diff = int(np.sum(xp != xr))
        log(f"[parity] paged_residual_flush {name}: {diff} of {xr.size} "
            f"elements differ {'ok' if diff == 0 else 'FAIL'}")
        if diff:
            failures.append(f"paged_residual_flush {name}")

    x = jax.random.normal(ks[6], (2, h, 4096, d), jnp.float32).astype(jnp.bfloat16)
    for bits, gran in ((4, "channel"), (4, "tensor")):
        wp, sp, zp = kvq.quantize_kv(x, bits, gran, block_n=block_n, impl="pallas")
        wr, sr, zr = kvq.quantize_kv(x, bits, gran, block_n=block_n, impl="xla")
        wdiff = int(np.sum(np.asarray(wp) != np.asarray(wr)))
        sp, sr, zp, zr = (np.asarray(a, np.float32) for a in (sp, sr, zp, zr))
        ok = (wdiff == 0 and np.allclose(sp, sr, **KVQ_PARAM_TOL)
              and np.allclose(zp, zr, **KVQ_PARAM_TOL))
        log(f"[parity] kv_quant bits={bits} {gran}: words differing={wdiff} "
            f"max|dscale|={np.abs(sp - sr).max():.3e} "
            f"max|dzero|={np.abs(zp - zr).max():.3e} {'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(f"kv_quant {gran}")
    _require(not failures, f"kernel parity failed: {failures}")


# ------------------------------------------------------------------ phase c


def make_requests(vocab: int, seed: int, lengths, max_new, *, shared_len: int):
    """Requests with seeded prompts.  The last one repeats the first
    ``shared_len`` tokens of the first prompt, so once the first is resident
    its prefill runs as a suffix prefill over the shared pages."""
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]
    tail = rng.integers(0, vocab, lengths[-1] - shared_len).astype(np.int32)
    prompts[-1] = np.concatenate([prompts[0][:shared_len], tail])
    return [Request(uid=i, prompt=p, max_new_tokens=m)
            for i, (p, m) in enumerate(zip(prompts, max_new))]


def serve_requests(engine, reqs):
    """Admit all but the last request, run one cycle so the first is
    resident, then admit the sharer and run to completion."""
    for r in reqs[:-1]:
        engine.submit(r)
    engine.step()
    engine.submit(reqs[-1])
    return engine.run()


def check_finished(reqs, vocab: int):
    for r in reqs:
        toks = np.asarray(r.out_tokens)
        _require(r.done and len(toks) == r.max_new_tokens,
                 f"request {r.uid} ended {r.phase} with {len(toks)} tokens")
        _require(bool(np.all((toks >= 0) & (toks < vocab))),
                 f"request {r.uid} emitted out-of-vocab tokens")


def serve(cfg, seed: int, *, lengths, max_new, shared_len, max_seq=MAX_SEQ):
    from repro.launch.serve import build_engine
    from repro.serve.scheduler import bucket_for

    t = time.perf_counter()
    engine = build_engine(cfg, seed=seed, slots=B_SLOTS, max_seq=max_seq)
    log(f"[serve] built engine in {time.perf_counter() - t:.1f}s: "
        f"pool={engine.n_pages} pages x {engine.kv_page_bytes} B")

    # compile the decode step and the largest prefill bucket up front, to
    # show the kernels the compiled programs hold (the persistent cache
    # serves the engine's own calls)
    toks = jax.ShapeDtypeStruct((B_SLOTS, 1), jnp.int32)
    t = time.perf_counter()
    dec = engine._step.lower(engine.params, engine.state, toks).compile()
    t_dec = time.perf_counter() - t
    bucket = bucket_for(max(lengths), min_bucket=engine.sched.min_bucket)
    t = time.perf_counter()
    pre = engine._prefill.lower(
        engine.params, jax.ShapeDtypeStruct((B_SLOTS, bucket), jnp.int32),
        jax.ShapeDtypeStruct((B_SLOTS,), jnp.int32)).compile()
    t_pre = time.perf_counter() - t
    k_dec, k_pre = mosaic_kernels(dec), mosaic_kernels(pre)
    log(f"[serve] compile: decode step {t_dec:.1f}s, prefill bucket {bucket} "
        f"{t_pre:.1f}s")
    log(f"[serve] Mosaic kernels in the compiled decode step: {k_dec}")
    log(f"[serve] Mosaic kernels in the compiled prefill step: {k_pre}")
    _require(k_dec.get("paged_bitdecode", 0) >= 1
             and k_dec.get("paged_residual_flush", 0) >= 1,
             f"decode step lacks the paged kernels: {k_dec}")
    _require(k_pre.get("kv_quant", 0) >= 1, f"prefill lacks kv_quant: {k_pre}")
    del dec, pre

    reqs = make_requests(cfg.vocab, seed, lengths, max_new, shared_len=shared_len)
    t = time.perf_counter()
    stats = serve_requests(engine, reqs)
    wall = time.perf_counter() - t
    check_finished(reqs, cfg.vocab)
    _require(stats["prefill_tokens_saved"] > 0, "the shared prefix was not reused")
    mem = jax.devices()[0].memory_stats() or {}
    log(f"[serve] {len(reqs)} requests finished: {stats['decoded_tokens']} tokens "
        f"in {wall:.2f}s ({stats['decoded_tokens'] / wall:.1f} tokens/s, "
        f"compiles included), ttft_p50={stats['ttft_p50_ms']:.1f}ms, "
        f"prefill_tokens_saved={stats['prefill_tokens_saved']}, "
        f"peak_bytes_in_use={mem.get('peak_bytes_in_use', 'not reported')}")
    return engine.model, engine.params


# ------------------------------------------------------------------ phase d


def xla_parity(model, params, seed: int, *, lengths=(1023, 1000, 900, 700, 600,
                                                    512, 300, 129),
               steps: int = 3, max_seq: int = 2048):
    """Prefill + ``steps`` decode steps through the Pallas kernels and
    through the XLA references, fed the same tokens; compare the logits.
    A prompt of 1023 tokens fills its residual on the first step, so the
    flush kernel runs too."""
    from repro.serve import pages as pg
    from repro.serve.scheduler import bucket_for

    cfg = model.cfg
    block_n = cfg.kv_block
    b = len(lengths)
    nb = max_seq // block_n
    bucket = bucket_for(max(lengths))
    rng = np.random.default_rng(seed + 1)
    toks = np.zeros((b, bucket), np.int32)
    for i, n in enumerate(lengths):
        toks[i, :n] = rng.integers(0, cfg.vocab, n)
    lens = jnp.asarray(lengths, jnp.int32)
    pages = [[b + i * nb + j for j in range(n // block_n)] for i, n in enumerate(lengths)]
    table = np.broadcast_to(np.arange(b, dtype=np.int32)[:, None], (b, nb)).copy()
    for i, pg_i in enumerate(pages):
        table[i, : len(pg_i)] = pg_i

    def run(impl, feed=None):
        logits, dense = jax.jit(lambda p, t, ln: model.prefill(
            p, {"tokens": t}, t.shape[1], lengths=ln, quant_impl=impl))(
                params, jnp.asarray(toks), lens)
        state = model.init_paged_decode_state(b, n_pages=b + b * nb, nb_max=nb)
        caches = pg.adopt_prefill(state["caches"], dense["caches"],
                                  slot_ids=list(range(b)), lengths=list(lengths),
                                  pages_per_req=pages, block_n=block_n)
        state = {"caches": pg.set_page_tables(caches, table), "pos": dense["pos"]}
        del dense
        step = jax.jit(lambda p, s, t: model.decode_step(
            p, s, t, impl=impl, quant_impl=impl))
        out, fed = [np.asarray(logits[:, -1], np.float32)], []
        for i in range(steps):
            tok = (np.argmax(out[-1], axis=-1).astype(np.int32)[:, None]
                   if feed is None else feed[i])
            fed.append(tok)
            logits, state = step(params, state, jnp.asarray(tok))
            out.append(np.asarray(logits[:, -1], np.float32))
        return out, fed

    got, fed = run("pallas")
    want, _ = run("xla", feed=fed)
    worst = 0.0
    for i, (a, r) in enumerate(zip(got, want)):
        rel = float(np.linalg.norm(a - r) / np.linalg.norm(r))
        worst = max(worst, rel)
        what = "prefill" if i == 0 else f"decode step {i}"
        log(f"[xla-parity] {what}: ||dlogits||/||logits|| = {rel:.3e}, "
            f"max|dlogit| = {np.abs(a - r).max():.3e}, "
            f"argmax agree {np.mean(a.argmax(-1) == r.argmax(-1)):.3f}")
        _require(bool(np.all(np.isfinite(a))), f"non-finite logits at {what}")
    _require(worst <= LOGIT_REL_TOL,
             f"Pallas vs XLA logits differ by {worst:.3e} > {LOGIT_REL_TOL}")


# ------------------------------------------------------------- four chips


def four_chips(cfg, seed: int, *, lengths, max_new, shared_len):
    """Split-KV serving on a (4,) "data" mesh: page-affine pools against the
    replicated-pool sharded walk, token for token."""
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import build_engine

    mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    outs = {}
    for affine in (True, False):
        engine = build_engine(cfg, seed=seed, slots=B_SLOTS, max_seq=MAX_SEQ,
                              mesh=mesh, splitkv="always", page_affine=affine)
        reqs = make_requests(cfg.vocab, seed, lengths, max_new,
                             shared_len=shared_len)
        t = time.perf_counter()
        stats = serve_requests(engine, reqs)
        check_finished(reqs, cfg.vocab)
        _require(stats["splitkv_steps"] > 0, "no split-KV step ran")
        kw = engine.state["caches"][0].kw
        shards = kw.addressable_shards
        per_chip = {str(s.device): sum(
            getattr(engine.state["caches"][0], f).addressable_shards[i].data.nbytes
            for f in ("kw", "k_scale", "k_zero", "vw", "v_scale", "v_zero"))
            for i, s in enumerate(shards)}
        label = "page-affine" if affine else "replicated"
        log(f"[4chip] {label} pools: {stats['splitkv_steps']} split-KV steps, "
            f"{stats['decoded_tokens']} tokens in {time.perf_counter() - t:.1f}s; "
            f"pool bytes per chip {per_chip}")
        if affine:
            _require(len({s.device for s in shards}) == 4,
                     f"pool shards sit on {[str(s.device) for s in shards]}")
            _require(all(s.data.shape[1] == kw.shape[1] // 4 for s in shards),
                     f"pool pages not split four ways: "
                     f"{[s.data.shape for s in shards]}")
        outs[affine] = [list(r.out_tokens) for r in reqs]
        del engine, kw, shards
        gc.collect()  # free this engine's device state before the next
    _require(outs[True] == outs[False],
             "page-affine pools changed the sharded walk's tokens")
    log(f"[4chip] page-affine tokens == replicated-pool tokens for "
        f"{len(outs[True])} requests")


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} attached",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} (using {args.chips}); compile cache {cache}")
    cfg = serving_config()
    log(f"[config] llama3-8b: d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab}; n_layers={cfg.n_layers} of 32 (cut so the bf16 "
        f"weights fit one chip); kv_bits={cfg.kv_bits} kv_block={cfg.kv_block} "
        f"k_gran={cfg.kv_gran}; seeded random weights")
    # the last request shares the first 1024 tokens of the first; its suffix
    # prefill attends the prior through an [S, prior + S] score tile per
    # head (ROADMAP S3), so its suffix stays short
    lengths = (2100, 700, 1000, 1500, 2600, 3900, 520, 1200)
    max_new = (32, 40, 48, 56, 64, 36, 44, 52)
    shared_len = 1024
    if args.chips == 4:
        four_chips(cfg, args.seed, lengths=lengths, max_new=max_new,
                   shared_len=shared_len)
    else:
        kernel_parity(args.seed)
        model, params = serve(cfg, args.seed, lengths=lengths, max_new=max_new,
                              shared_len=shared_len)
        gc.collect()  # the engine's pools go before phase d builds its own
        xla_parity(model, params, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
