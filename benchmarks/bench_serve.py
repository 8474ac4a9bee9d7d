"""Serving-throughput sweeps for the paged continuous-batching engine.

Seven sweeps, all appending to BENCH_serve.json so future PRs track them:

* **offered load** (default): requests arrive on a virtual clock (the
  measured engine wall time) at a configured rate with a prompt-length mix;
  each (rate x mix) cell reports end-to-end tokens/s, per-token latency
  percentiles, scheduler backpressure counts, and page-pool occupancy.
* **shared prefix** (``--shared-prefix``): a shared-fraction x prompt-length
  grid where every request's prompt begins with a common template prefix;
  each cell reports the prefix-index hit rate, prefill tokens actually
  computed vs. served from resident pages, pool pages used with vs. without
  sharing, and copy-on-write counts — the serving face of the prefix-sharing
  tentpole (docs/SERVING.md §4-5).
* **cache families** (``--family {attn,mla,hybrid}``): the same mixed
  workload through the unified paged engine per cache family — plain/GQA
  K/V pools, MLA shared-kv latent pools, hybrid paged-attention +
  dense SSM side-state — reporting per-family throughput, latency, and the
  per-family page byte size (``kv_page_bytes``; a hybrid page spans
  ``n_super`` layer-caches, an MLA page has no V stream).
* **oversubscription** (``--oversubscribe``): the pool capped at
  0.5x/0.75x/1.0x of the workload's worst-case concurrent page demand under
  ``reserve_policy="expected"`` — the pressure face of
  preemption-by-rematerialization (docs/SERVING.md §10): each cell reports
  the preemption rate, replayed (rematerialized) tokens, tokens/s, and
  occupancy, with the invariant auditor enabled every cycle.
* **self-speculative decoding** (``--spec-decode``): spec_k x spec_bits
  against the sequential baseline (docs/SERVING.md §11) — accepted-token
  rate, tokens per cycle, end-to-end speedup, and a bitwise-parity check
  of every output stream.
* **async runtime** (``--async-sweep``): the same offered-load curve
  through ``async_runtime=False`` and ``True`` (docs/SERVING.md §13) —
  per-cell tokens/s and ``host_stall_fraction`` before/after overlap, plus
  a bitwise-parity check; the acceptance bar is the async stall fraction
  strictly below the sync baseline on the same workload.
* **tenant churn** (``--tenant-churn``): rotating sessions over per-tenant
  shared system prompts where every session departs before the tenant's
  next arrives (docs/SERVING.md §14) — prefix hit rate, prefill tokens
  computed vs. saved, retained-hit and retained-reclaim counts with
  retention off and on, over an ample and a deliberately tight pool, plus
  the §14 bitwise oracles (cold first round, retained-hit == live-hit).

Telemetry (docs/OBSERVABILITY.md): every offered-load cell reports TTFT and
TPOT percentiles (split latency series — queueing shows up in TTFT, steady
decode in TPOT) and the host-stall fraction (share of each cycle NOT spent
waiting on the device).  ``--phase-breakdown`` adds the per-phase seconds
(schedule / prefill / decode_dispatch / device_wait / logits_pull / sample
/ advance) to each record; ``--trace-out PATH`` traces the first cell and
writes a Chrome ``trace_event`` JSON openable in Perfetto.

CPU smoke scale by default; the same sweeps run unchanged on TPU.
"""
from __future__ import annotations

import json
import zlib
from pathlib import Path

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs.base import smoke_config
from repro.launch import serve as _serve_cli
from repro.models.zoo import build_model
from repro.serve.engine import Request, ServeEngine

_BENCH_SERVE = Path(__file__).resolve().parent.parent / "BENCH_serve.json"

# prompt-length mixes: (name, [(length, weight), ...])
_MIXES = [
    ("short", [(8, 0.7), (24, 0.3)]),
    ("mixed", [(8, 0.5), (48, 0.35), (96, 0.15)]),
]


def _make_requests(n, mix, max_new, vocab, rate_rps, rng):
    lengths = [l for l, _ in mix]
    weights = np.asarray([w for _, w in mix])
    weights = weights / weights.sum()
    # deterministic arrival spacing at the offered rate, jittered
    gaps = rng.exponential(1.0 / rate_rps, size=n)
    arrivals = np.cumsum(gaps)
    reqs = []
    for i in range(n):
        plen = int(rng.choice(lengths, p=weights))
        reqs.append(Request(
            uid=i,
            prompt=rng.integers(0, vocab, plen).astype(np.int32),
            max_new_tokens=max_new,
            arrival_s=float(arrivals[i]),
        ))
    return reqs


def run_serve_sweep(*, n_requests=8, max_new=8, slots=4, max_seq=256,
                    rates=(2.0, 16.0), out_path: Path | None = None,
                    time_scale=1.0, phase_breakdown=False,
                    trace_out: Path | None = None):
    """Offered-load sweep: rate (requests/s on the virtual clock) x prompt
    mix.  ``time_scale`` stretches the virtual clock (CPU cycles are slow;
    scale keeps arrival dynamics interesting at smoke sizes).
    ``phase_breakdown`` adds per-phase seconds to every record;
    ``trace_out`` traces the first cell into a Chrome trace_event JSON."""
    cfg = smoke_config("llama3-8b").with_(kv_bits=4, kv_block=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    records = []
    first_cell = True
    for mix_name, mix in _MIXES:
        for rate in rates:
            # deterministic per-cell seed (str hash is salted per process)
            rng = np.random.default_rng(zlib.crc32(f"{mix_name}:{rate}".encode()))
            reqs = _make_requests(n_requests, mix, max_new, cfg.vocab, rate, rng)
            trace_cell = trace_out is not None and first_cell
            first_cell = False
            engine = ServeEngine(model, params, slots=slots, max_seq=max_seq,
                                 trace=trace_cell)
            pending = sorted(reqs, key=lambda r: r.arrival_s)
            import time as _time

            t0 = _time.perf_counter()
            cycles = 0
            while pending or engine._has_work():
                now = (_time.perf_counter() - t0) * time_scale
                while pending and pending[0].arrival_s <= now:
                    engine.submit(pending.pop(0))
                if not engine._has_work():
                    # idle gap before the next arrival: jump the virtual clock
                    if pending:
                        engine.submit(pending.pop(0))
                    continue
                engine.step()
                cycles += 1
                if cycles > 20_000:
                    break
            stats = engine.summary(wall_s=_time.perf_counter() - t0)
            rec = {
                "mix": mix_name,
                "offered_rate_rps": rate,
                "n_requests": n_requests,
                "slots": slots,
                "decoded_tokens": stats["decoded_tokens"],
                "tokens_per_s": round(stats["tokens_per_s"], 2),
                "latency_p50_ms": round(stats["latency_p50_ms"], 2),
                "latency_p99_ms": round(stats["latency_p99_ms"], 2),
                "prefill_calls": stats["prefill_calls"],
                "backpressure_events": stats["sched_backpressure_events"],
                "occupancy_mean": round(stats["occupancy_mean"], 4),
                "occupancy_max": round(stats["occupancy_max"], 4),
                "ttft_p50_ms": round(stats["ttft_p50_ms"], 2),
                "ttft_p99_ms": round(stats["ttft_p99_ms"], 2),
                "tpot_p50_ms": round(stats["tpot_p50_ms"], 3),
                "tpot_p99_ms": round(stats["tpot_p99_ms"], 3),
                "queue_wait_p50_ms": round(stats["queue_wait_p50_ms"], 2),
                "host_stall_fraction": round(stats["host_stall_fraction"], 4),
            }
            if phase_breakdown:
                rec["phase_s"] = {
                    k: round(v, 5) for k, v in stats["phase_s"].items()
                }
            if trace_cell:
                engine.tracer.write_chrome(Path(trace_out))
                print(f"[bench_serve] trace ({mix_name} @ {rate:g} rps) -> "
                      f"{trace_out}")
            records.append(rec)
            emit(
                f"serve.{mix_name}.rps{rate:g}", stats["latency_p50_ms"] * 1e3,
                f"tok/s={rec['tokens_per_s']};p99_ms={rec['latency_p99_ms']}"
                f";occ_max={rec['occupancy_max']};prefills={rec['prefill_calls']}"
                f";ttft_p50_ms={rec['ttft_p50_ms']}"
                f";tpot_p50_ms={rec['tpot_p50_ms']}"
                f";host_stall={rec['host_stall_fraction']}",
            )
    out_path = _BENCH_SERVE if out_path is None else out_path
    _append(out_path, {"backend": jax.default_backend(), "records": records})
    return records


def _append(out_path: Path, entry: dict) -> None:
    history = []
    if out_path.exists():
        try:
            history = json.loads(out_path.read_text())
        except json.JSONDecodeError:
            history = []
    history.append(entry)
    out_path.write_text(json.dumps(history, indent=2) + "\n")


def run_shared_prefix_sweep(*, shared_fracs=(0.0, 0.5, 0.9),
                            prompt_lens=(64, 128), n_requests=6, max_new=6,
                            slots=4, max_seq=256,
                            out_path: Path | None = None):
    """Shared-fraction x prompt-length grid through the prefix-sharing
    engine: every request's prompt starts with the same template prefix of
    ``frac * plen`` tokens (rounded down to whole ``kv_block`` blocks — the
    sharing granularity), followed by a private tail.  The first request is
    admitted alone so its pages register before the rest arrive (sharing is
    cross-cycle by design, docs/SERVING.md §4)."""
    cfg = smoke_config("llama3-8b").with_(kv_bits=4, kv_block=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    records = []
    for plen in prompt_lens:
        for frac in shared_fracs:
            rng = np.random.default_rng(
                zlib.crc32(f"shared:{plen}:{frac}".encode())
            )
            shared_len = int(frac * plen) // cfg.kv_block * cfg.kv_block
            prefix = rng.integers(0, cfg.vocab, shared_len).astype(np.int32)
            reqs = [
                Request(
                    uid=i,
                    prompt=np.concatenate([
                        prefix,
                        rng.integers(0, cfg.vocab, plen - shared_len).astype(np.int32),
                    ]),
                    max_new_tokens=max_new,
                )
                for i in range(n_requests)
            ]
            engine = ServeEngine(model, params, slots=slots, max_seq=max_seq)
            import time as _time

            t0 = _time.perf_counter()
            engine.submit(reqs[0])
            engine.step()  # register the template prefix
            for r in reqs[1:]:
                engine.submit(r)
            engine.run()
            stats = engine.summary(wall_s=_time.perf_counter() - t0)
            rec = {
                "prompt_len": plen,
                "shared_frac": frac,
                "shared_blocks": shared_len // cfg.kv_block,
                "n_requests": n_requests,
                "prefill_tokens": stats["prefill_tokens"],
                "prefill_tokens_saved": stats["prefill_tokens_saved"],
                "prefix_hit_rate": round(stats["prefix_hit_rate"], 4),
                "prefix_hit_requests": stats["sched_prefix_hit_requests"],
                "spec_tail_adoptions": stats["sched_spec_tail_adoptions"],
                "cow_copies": stats["cow_copies"],
                "tokens_per_s": round(stats["tokens_per_s"], 2),
                "occupancy_max": round(stats["occupancy_max"], 4),
            }
            records.append(rec)
            emit(
                f"serve.shared.L{plen}.f{frac:g}",
                stats["prefill_tokens"],
                f"saved={rec['prefill_tokens_saved']}"
                f";hit_rate={rec['prefix_hit_rate']}"
                f";cow={rec['cow_copies']};tok/s={rec['tokens_per_s']}",
            )
    out_path = _BENCH_SERVE if out_path is None else out_path
    _append(out_path, {
        "backend": jax.default_backend(),
        "sweep": "shared_prefix",
        "records": records,
    })
    return records


# representative archs per cache family — shared with the serving CLI so
# the bench rows always exercise what `repro.launch.serve --family` runs
# (xlstm is CLI-only: the shim has no page accounting to sweep)
_FAMILY_ARCHS = {
    f: a for f, a in _serve_cli.FAMILY_ARCHS.items() if f != "xlstm"
}


def run_family_sweep(*, families=("attn", "mla", "hybrid"), n_requests=6,
                     max_new=8, slots=4, max_seq=256,
                     out_path: Path | None = None):
    """Per-cache-family serving sweep through the unified paged engine: the
    same mixed prompt-length workload per family, throughput/latency plus
    the per-family page accounting (kv_page_bytes differs: a hybrid page
    spans only the shared-attention invocations, an MLA latent page has no
    V stream at all)."""
    records = []
    for family in families:
        cfg = smoke_config(_FAMILY_ARCHS[family]).with_(kv_bits=4, kv_block=32)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(zlib.crc32(f"family:{family}".encode()))
        plens = [8, 40, 70]
        reqs = [
            Request(
                uid=i,
                prompt=rng.integers(0, cfg.vocab, plens[i % len(plens)]).astype(np.int32),
                max_new_tokens=max_new,
            )
            for i in range(n_requests)
        ]
        engine = ServeEngine(model, params, slots=slots, max_seq=max_seq)
        import time as _time

        t0 = _time.perf_counter()
        for r in reqs:
            engine.submit(r)
        engine.run()
        stats = engine.summary(wall_s=_time.perf_counter() - t0)
        rec = {
            "family": family,
            "arch": cfg.name,
            "paged": engine.paged,
            "shared_kv": bool(engine.spec.shared_kv),
            "exact_prefill": bool(engine.spec.exact_prefill),
            "n_requests": n_requests,
            "decoded_tokens": stats["decoded_tokens"],
            "tokens_per_s": round(stats["tokens_per_s"], 2),
            "latency_p50_ms": round(stats["latency_p50_ms"], 2),
            "latency_p99_ms": round(stats["latency_p99_ms"], 2),
            "prefill_calls": stats["prefill_calls"],
            "kv_page_bytes": stats["kv_page_bytes"],
            "occupancy_max": round(stats["occupancy_max"], 4),
        }
        records.append(rec)
        emit(
            f"serve.family.{family}", stats["latency_p50_ms"] * 1e3,
            f"tok/s={rec['tokens_per_s']};p99_ms={rec['latency_p99_ms']}"
            f";page_B={rec['kv_page_bytes']};prefills={rec['prefill_calls']}",
        )
    out_path = _BENCH_SERVE if out_path is None else out_path
    _append(out_path, {
        "backend": jax.default_backend(),
        "sweep": "family",
        "records": records,
    })
    return records


def run_oversubscribe_sweep(*, factors=(0.5, 0.75, 1.0), n_requests=6,
                            max_new=24, slots=2, max_seq=128,
                            out_path: Path | None = None):
    """Pressure sweep: the data-page pool capped at ``factor`` x the
    workload's worst-case concurrent page demand (the top-``slots``
    per-request page totals), run under ``reserve_policy="expected"`` with
    the most aggressive quantile (0.0 — reserve only what is certain).
    Undersized cells force preemption-by-rematerialization; every cell also
    runs once against an ample pool and checks the outputs are bitwise
    identical (docs/SERVING.md §10), with the invariant auditor on every
    cycle."""
    cfg = smoke_config("llama3-8b").with_(kv_bits=4, kv_block=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plens = [34, 48, 40, 44, 36, 46]

    def _reqs(rng):
        return [
            Request(
                uid=i,
                prompt=rng.integers(
                    0, cfg.vocab, plens[i % len(plens)]).astype(np.int32),
                max_new_tokens=max_new,
            )
            for i in range(n_requests)
        ]

    import math
    import time as _time

    # worst-case concurrent demand: the `slots` largest per-request totals
    per_req = sorted(
        ((p + max_new) // cfg.kv_block for p in plens[:n_requests]),
        reverse=True,
    )
    worst = sum(per_req[:slots])

    # unpressured reference outputs (ample pool, worst-case reservations)
    base = ServeEngine(model, params, slots=slots, max_seq=max_seq)
    base_reqs = _reqs(np.random.default_rng(zlib.crc32(b"oversub")))
    for r in base_reqs:
        base.submit(r)
    base.run()
    base_out = {r.uid: list(r.out_tokens) for r in base_reqs}

    records = []
    for factor in factors:
        rng = np.random.default_rng(zlib.crc32(b"oversub"))
        n_pages = slots + math.ceil(factor * worst)
        engine = ServeEngine(
            model, params, slots=slots, max_seq=max_seq, n_pages=n_pages,
            reserve_policy="expected", expected_quantile=0.0, audit_every=1,
        )
        reqs = _reqs(rng)
        t0 = _time.perf_counter()
        for r in reqs:
            engine.submit(r)
        engine.run()
        stats = engine.summary(wall_s=_time.perf_counter() - t0)
        out = {r.uid: list(r.out_tokens) for r in reqs}
        rec = {
            "oversubscribe": factor,
            "n_pages": n_pages - slots,
            "worst_case_pages": worst,
            "n_requests": n_requests,
            "slots": slots,
            "preempted": stats["preempted"],
            "preemptions_per_request": round(
                stats["preempted"] / n_requests, 4),
            "preempt_remat_tokens": stats["preempt_remat_tokens"],
            "decoded_tokens": stats["decoded_tokens"],
            "tokens_per_s": round(stats["tokens_per_s"], 2),
            "latency_p50_ms": round(stats["latency_p50_ms"], 2),
            "latency_p99_ms": round(stats["latency_p99_ms"], 2),
            "backpressure_events": stats["sched_backpressure_events"],
            "occupancy_max": round(stats["occupancy_max"], 4),
            "audits": stats["audits"],
            "bitwise_match": out == base_out,
        }
        records.append(rec)
        emit(
            f"serve.oversub.x{factor:g}", stats["tokens_per_s"],
            f"preempted={rec['preempted']}"
            f";remat_tok={rec['preempt_remat_tokens']}"
            f";p99_ms={rec['latency_p99_ms']}"
            f";match={rec['bitwise_match']}",
        )
    out_path = _BENCH_SERVE if out_path is None else out_path
    _append(out_path, {
        "backend": jax.default_backend(),
        "sweep": "oversubscribe",
        "records": records,
    })
    return records


def run_spec_decode_sweep(*, spec_ks=(2, 4), spec_bits=(2, 4), n_requests=6,
                          max_new=16, slots=2, max_seq=128,
                          out_path: Path | None = None):
    """Self-speculative decoding sweep (docs/SERVING.md §11): spec_k x
    spec_bits against the sequential ``spec_k=1`` baseline over the same
    workload.  Each cell reports the accepted-token rate (the fraction of
    truncated-bit draft tokens the full-fidelity verify kept), end-to-end
    tokens/s, the speedup over sequential decode, and a bitwise-parity
    check of every output stream — speculation must never change tokens,
    only the number of host round-trips per token."""
    cfg = smoke_config("llama3-8b").with_(kv_bits=4, kv_block=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plens = [34, 48, 40, 44, 36, 46]

    def _reqs():
        rng = np.random.default_rng(zlib.crc32(b"specdec"))
        return [
            Request(
                uid=i,
                prompt=rng.integers(
                    0, cfg.vocab, plens[i % len(plens)]).astype(np.int32),
                max_new_tokens=max_new,
            )
            for i in range(n_requests)
        ]

    import time as _time

    base = ServeEngine(model, params, slots=slots, max_seq=max_seq)
    base_reqs = _reqs()
    t0 = _time.perf_counter()
    for r in base_reqs:
        base.submit(r)
    base.run()
    base_stats = base.summary(wall_s=_time.perf_counter() - t0)
    base_out = {r.uid: list(r.out_tokens) for r in base_reqs}
    base_tps = base_stats["tokens_per_s"]

    records = [{
        "spec_k": 1,
        "tokens_per_s": round(base_tps, 2),
        "decoded_tokens": base_stats["decoded_tokens"],
        "steps": base_stats["steps"],
    }]
    for k in spec_ks:
        for bits in spec_bits:
            engine = ServeEngine(
                model, params, slots=slots, max_seq=max_seq,
                spec_k=k, spec_bits=bits,
            )
            reqs = _reqs()
            t0 = _time.perf_counter()
            for r in reqs:
                engine.submit(r)
            engine.run()
            stats = engine.summary(wall_s=_time.perf_counter() - t0)
            out = {r.uid: list(r.out_tokens) for r in reqs}
            rec = {
                "spec_k": k,
                "spec_bits": bits,
                "n_requests": n_requests,
                "slots": slots,
                "decoded_tokens": stats["decoded_tokens"],
                "steps": stats["steps"],
                "spec_cycles": stats["spec_cycles"],
                "spec_draft_tokens": stats["spec_draft_tokens"],
                "spec_accepted_tokens": stats["spec_accepted_tokens"],
                "accept_rate": round(stats["spec_accept_rate"], 4),
                "tokens_per_cycle": round(
                    stats["decoded_tokens"] / max(1, stats["steps"]), 3),
                "tokens_per_s": round(stats["tokens_per_s"], 2),
                "speedup_vs_sequential": round(
                    stats["tokens_per_s"] / max(base_tps, 1e-9), 3),
                "bitwise_match": out == base_out,
            }
            records.append(rec)
            emit(
                f"serve.spec.k{k}.b{bits}", stats["tokens_per_s"],
                f"accept={rec['accept_rate']}"
                f";tok/cyc={rec['tokens_per_cycle']}"
                f";speedup={rec['speedup_vs_sequential']}"
                f";match={rec['bitwise_match']}",
            )
    out_path = _BENCH_SERVE if out_path is None else out_path
    _append(out_path, {
        "backend": jax.default_backend(),
        "sweep": "spec_decode",
        "records": records,
    })
    return records


def run_async_sweep(*, rates=(2.0, 8.0, 16.0), n_requests=8, max_new=12,
                    slots=4, max_seq=256, time_scale=1.0,
                    out_path: Path | None = None):
    """Async-vs-sync offered-load curve (docs/SERVING.md §13): each rate
    cell drives the identical workload through the synchronous cycle and
    the overlapped runtime, recording tokens/s and ``host_stall_fraction``
    for both, a bitwise-parity check of the streams, and the async-side
    pipeline counters (in-flight window depth, discarded steps, starvation
    seconds).  The ISSUE 9 acceptance bar reads straight off these rows:
    async ``host_stall_fraction`` strictly below sync on the same cell."""
    cfg = smoke_config("llama3-8b").with_(kv_bits=4, kv_block=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mix_name, mix = _MIXES[1]  # the mixed prompt-length workload

    import time as _time

    records = []
    for rate in rates:
        outs = {}
        for runtime in ("sync", "async"):
            rng = np.random.default_rng(
                zlib.crc32(f"async:{mix_name}:{rate}".encode())
            )
            reqs = _make_requests(
                n_requests, mix, max_new, cfg.vocab, rate, rng
            )
            engine = ServeEngine(
                model, params, slots=slots, max_seq=max_seq,
                async_runtime=(runtime == "async"),
            )
            pending = sorted(reqs, key=lambda r: r.arrival_s)
            t0 = _time.perf_counter()
            cycles = 0
            while pending or engine._has_work():
                now = (_time.perf_counter() - t0) * time_scale
                while pending and pending[0].arrival_s <= now:
                    engine.submit(pending.pop(0))
                if not engine._has_work():
                    if pending:
                        engine.submit(pending.pop(0))
                    continue
                engine.step()
                cycles += 1
                if cycles > 20_000:
                    break
            stats = engine.summary(wall_s=_time.perf_counter() - t0)
            engine.close()
            outs[runtime] = {r.uid: list(r.out_tokens) for r in reqs}
            rec = {
                "mix": mix_name,
                "offered_rate_rps": rate,
                "runtime": runtime,
                "n_requests": n_requests,
                "slots": slots,
                "decoded_tokens": stats["decoded_tokens"],
                "tokens_per_s": round(stats["tokens_per_s"], 2),
                "host_stall_fraction": round(
                    stats["host_stall_fraction"], 4),
                "ttft_p50_ms": round(stats["ttft_p50_ms"], 2),
                "tpot_p50_ms": round(stats["tpot_p50_ms"], 3),
            }
            if runtime == "async":
                rec["discarded_steps"] = stats["discarded_steps"]
                rec["completions_enqueued"] = stats["completions_enqueued"]
                rec["device_starved_s"] = round(
                    engine.metrics.hist("device_starved_s").total, 5)
                rec["bitwise_match"] = outs["async"] == outs["sync"]
            records.append(rec)
            emit(
                f"serve.async.rps{rate:g}.{runtime}",
                stats["tokens_per_s"],
                f"host_stall={rec['host_stall_fraction']}"
                f";tpot_p50_ms={rec['tpot_p50_ms']}"
                + (f";match={rec['bitwise_match']}"
                   f";discarded={rec['discarded_steps']}"
                   if runtime == "async" else ""),
            )
    out_path = _BENCH_SERVE if out_path is None else out_path
    _append(out_path, {
        "backend": jax.default_backend(),
        "sweep": "async_runtime",
        "records": records,
    })
    return records


def run_tenant_churn_sweep(*, n_tenants=3, rounds=2, max_new=26, slots=2,
                           max_seq=256, out_path: Path | None = None):
    """Tenant-churn sweep (docs/SERVING.md §14): ``n_tenants`` tenants, each
    with a fixed two-block system prompt, rotate short sessions through the
    engine one at a time — every session fully departs before the tenant's
    next one arrives, so without retention the shared prompt is re-prefilled
    every visit.  The rotation is skewed (tenant 0 returns between every
    other tenant's session — the popular-system-prompt shape), which under
    the tight pool keeps the hot tenant's retained set MRU while the cold
    tenants' sets are LRU-reclaimed: the tight cell shows *graceful*
    degradation (partial hit rate, nonzero reclaims, zero preemptions)
    rather than all-or-nothing.  Each pool cell runs the identical session
    stream with retention off and on, recording the prefix hit rate,
    prefill tokens computed vs. saved, retained-hit and retained-reclaim
    counts.

    Bitwise claims recorded per the §14 oracle doctrine (§9: sharing itself
    is not bitwise vs. a cold raw-bf16 prefill, so ON-vs-OFF full-stream
    identity is not the bar): cold first visits are identical with
    retention on and off, and a retained hit emits exactly the tokens of a
    *live* hit on the same prompt (donor still resident)."""
    cfg = smoke_config("llama3-8b").with_(kv_bits=4, kv_block=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    blk = cfg.kv_block

    # hot/cold rotation: [0, 1, 0, 2, ..., 0, n-1] per round
    schedule = []
    for _ in range(rounds):
        for cold in range(1, n_tenants):
            schedule += [0, cold]
    max_occ = max(schedule.count(t) for t in range(n_tenants))

    rng = np.random.default_rng(zlib.crc32(b"tenant-churn"))
    system = [
        rng.integers(0, cfg.vocab, 2 * blk).astype(np.int32)
        for _ in range(n_tenants)
    ]
    tails = [
        [rng.integers(0, cfg.vocab, 8 + 3 * o).astype(np.int32)
         for o in range(max_occ)]
        for _ in range(n_tenants)
    ]

    def sessions():
        """Churn stream: (uid, tenant, occurrence, prompt)."""
        occ = [0] * n_tenants
        for uid, t in enumerate(schedule):
            yield uid, t, occ[t], np.concatenate([system[t], tails[t][occ[t]]])
            occ[t] += 1

    def churn_run(retain, n_pages):
        engine = ServeEngine(
            model, params, slots=slots, max_seq=max_seq, n_pages=n_pages,
            retain_prefix=retain,
        )
        import time as _time

        outs = {}
        t0 = _time.perf_counter()
        for uid, t, o, prompt in sessions():
            req = Request(uid=uid, prompt=prompt, max_new_tokens=max_new)
            engine.submit(req)
            engine.run()  # session completes and departs before the next
            outs[(t, o)] = list(req.out_tokens)
        return engine.summary(wall_s=_time.perf_counter() - t0), outs

    # live-hit oracle for tenant 0's round-1 session: round 0 still
    # resident (long decode) when the round-1 prompt admits, retention off
    live = ServeEngine(model, params, slots=slots, max_seq=max_seq)
    la = Request(uid=0, prompt=np.concatenate([system[0], tails[0][0]]),
                 max_new_tokens=48)
    lb = Request(uid=1, prompt=np.concatenate([system[0], tails[0][1]]),
                 max_new_tokens=max_new)
    live.submit(la)
    live.step()
    live.submit(lb)
    live.run()
    live_hit_tokens = list(lb.out_tokens)

    # tight pool: capacity equals the aggregate retained footprint (2 full
    # system blocks per tenant); each session's decode crosses into a third
    # block (max_new spans a block boundary), so admissions on a fully
    # populated tier must reclaim LRU retained pages first
    tight = slots + 2 * n_tenants
    records = []
    for pool_name, n_pages in (("ample", None), ("tight", tight)):
        cell = {}
        for retain in (False, True):
            stats, outs = churn_run(retain, n_pages)
            cell[retain] = (stats, outs)
            rec = {
                "pool": pool_name,
                "retention": retain,
                "n_tenants": n_tenants,
                "rounds": rounds,
                "sessions": len(schedule),
                "prefix_hit_rate": round(stats["prefix_hit_rate"], 4),
                "prefill_tokens": stats["prefill_tokens"],
                "prefill_tokens_saved": stats["prefill_tokens_saved"],
                "prefix_retained_hits": stats["sched_prefix_retained_hits"],
                "retained_reclaims": stats["retained_reclaims"],
                "pool_pages_retained": stats["pool_pages_retained"],
                "preempted": stats["preempted"],
                "tokens_per_s": round(stats["tokens_per_s"], 2),
            }
            if retain:
                off_stats, off_outs = cell[False]
                on_outs = outs
                rec["hit_rate_gain"] = round(
                    stats["prefix_hit_rate"] - off_stats["prefix_hit_rate"],
                    4)
                rec["prefill_tokens_delta"] = (
                    stats["prefill_tokens"] - off_stats["prefill_tokens"])
                # cold first visits identical with retention on and off
                rec["first_visit_bitwise_match"] = all(
                    on_outs[(t, 0)] == off_outs[(t, 0)]
                    for t in range(n_tenants)
                )
                if pool_name == "ample":
                    # retained hit == live hit, bitwise (§14 oracle)
                    rec["retained_hit_matches_live_hit"] = (
                        on_outs[(0, 1)] == live_hit_tokens
                    )
            records.append(rec)
            emit(
                f"serve.churn.{pool_name}.{'on' if retain else 'off'}",
                stats["prefill_tokens"],
                f"hit_rate={rec['prefix_hit_rate']}"
                f";saved={rec['prefill_tokens_saved']}"
                f";retained_hits={rec['prefix_retained_hits']}"
                f";reclaims={rec['retained_reclaims']}",
            )
    out_path = _BENCH_SERVE if out_path is None else out_path
    _append(out_path, {
        "backend": jax.default_backend(),
        "sweep": "tenant_churn",
        "records": records,
    })
    return records


def run():
    run_serve_sweep(phase_breakdown=True)
    run_shared_prefix_sweep()
    run_family_sweep()
    run_oversubscribe_sweep()
    run_spec_decode_sweep()
    run_async_sweep()
    run_tenant_churn_sweep()


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--shared-prefix", action="store_true",
                    help="run only the shared-prefix grid")
    ap.add_argument("--family", nargs="*", choices=sorted(_FAMILY_ARCHS),
                    default=None,
                    help="run only the cache-family sweep (optionally a "
                         "subset of families)")
    ap.add_argument("--oversubscribe", action="store_true",
                    help="run only the pool-pressure sweep (0.5x/0.75x/1.0x "
                         "of worst-case page demand)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="run only the self-speculative decoding sweep "
                         "(spec_k x spec_bits vs the sequential baseline)")
    ap.add_argument("--async-sweep", action="store_true",
                    help="run only the async-vs-sync offered-load curve "
                         "(tokens/s + host_stall_fraction per runtime)")
    ap.add_argument("--tenant-churn", action="store_true",
                    help="run only the tenant-churn sweep (rotating "
                         "sessions over shared system prompts, retention "
                         "off vs on, ample vs tight pool)")
    ap.add_argument("--phase-breakdown", action="store_true",
                    help="add per-phase seconds (schedule/prefill/"
                         "decode_dispatch/device_wait/logits_pull/sample/"
                         "advance) to every offered-load record "
                         "(docs/OBSERVABILITY.md)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="trace the first offered-load cell and write a "
                         "Chrome trace_event JSON (open in Perfetto)")
    args = ap.parse_args()
    if args.shared_prefix:
        run_shared_prefix_sweep()
    elif args.oversubscribe:
        run_oversubscribe_sweep()
    elif args.spec_decode:
        run_spec_decode_sweep()
    elif args.async_sweep:
        run_async_sweep()
    elif args.tenant_churn:
        run_tenant_churn_sweep()
    elif args.family is not None:
        run_family_sweep(
            families=tuple(args.family) if args.family else
            ("attn", "mla", "hybrid"))
    elif args.phase_breakdown or args.trace_out:
        run_serve_sweep(
            phase_breakdown=args.phase_breakdown,
            trace_out=Path(args.trace_out) if args.trace_out else None,
        )
    else:
        run()
