"""Serving launcher: continuous-batching paged serving with the quantized
KV cache — every cache family decodes through the page table (plain/GQA
attention, MLA latent pools, hybrid Mamba2+attention; no-KV recurrent
models serve through the exact-length shim).

Usage (CPU demo with a reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --requests 16 --slots 4 --max-new 24
  PYTHONPATH=src python -m repro.launch.serve --family mla --smoke \
      --requests 8

``--family {attn,mla,hybrid,xlstm}`` picks a representative arch for the
cache family (llama3-8b / deepseek-v3-671b / zamba2-7b / xlstm-1.3b) so the
unified paged engine is exercisable from the CLI for all families.

Page-pool sizing: --pages bounds the KV pool; by default the pool is fully
provisioned (slots * max_seq worth of pages).  Undersize it (e.g.
--pages 12) to exercise admission backpressure: requests wait in the queue
until completions return pages.  With ``--reserve-policy expected`` the
scheduler admits against a quantile of the remaining decode budget instead
of the worst case; if the pool later runs dry the engine preempts a victim
(``--preempt-policy``) and rematerializes it bitwise-identically on
re-admission (docs/SERVING.md §10).  ``--audit-every N`` cross-checks the
pool/page-table/prefix-index invariants every N cycles.  ``--spec-k K``
(K > 1) turns on self-speculative decoding: K-token greedy drafts read the
same committed pools at ``--spec-bits`` precision and a single batched
full-fidelity pass verifies them, keeping the output stream bitwise equal
to sequential decode (docs/SERVING.md §11).

Telemetry (docs/OBSERVABILITY.md): ``--trace-out trace.json`` records every
request lifecycle span and engine phase slice and writes a Chrome
``trace_event`` file (open in Perfetto / chrome://tracing) plus a
``.jsonl`` sibling with the raw events.  ``--metrics-every N`` prints the
Prometheus text exposition of the metrics registry every N cycles.  The
summary line always includes TTFT/TPOT percentiles and the host-stall
fraction (share of each decode cycle NOT spent waiting on the device).
"""
from __future__ import annotations

import argparse
import pathlib

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.zoo import build_model
from repro.serve.engine import Request, ServeEngine

FAMILY_ARCHS = {
    "attn": "llama3-8b",
    "mla": "deepseek-v3-671b",
    "hybrid": "zamba2-7b",
    "xlstm": "xlstm-1.3b",
}


def build_engine(cfg, *, seed: int = 0, **engine_kwargs) -> ServeEngine:
    """Build ``cfg``'s model with seeded random weights and a
    :class:`ServeEngine` over it (``engine_kwargs`` go to the engine).  With
    a ``mesh``, the weights are replicated over it once, up front."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    mesh = engine_kwargs.get("mesh")
    if mesh is not None:
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    return ServeEngine(model, params, **engine_kwargs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="explicit architecture (overrides --family)")
    ap.add_argument("--family", choices=sorted(FAMILY_ARCHS), default=None,
                    help="serve a representative arch of this cache family")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--kv-bits", type=int, default=4)
    ap.add_argument("--pages", type=int, default=None,
                    help="page-pool size (default: fully provisioned)")
    ap.add_argument("--dense", action="store_true",
                    help="force the exact-length shim (dense decode state)")
    ap.add_argument("--splitkv", choices=("auto", "always", "never"),
                    default="auto", help="cross-chip split-KV routing policy")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="give every prompt a common template prefix of this "
                         "many tokens so the prefix index reuses resident "
                         "pages (docs/SERVING.md)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable the scheduler's prompt-prefix index")
    ap.add_argument("--reserve-policy", choices=("worst_case", "expected"),
                    default="worst_case",
                    help="admission reservation: full lifetime worst case, "
                         "or a quantile of the remaining decode budget "
                         "(backed by preemption-by-rematerialization)")
    ap.add_argument("--expected-quantile", type=float, default=0.5,
                    help="decode-budget quantile reserved under "
                         "--reserve-policy expected (0=only what is certain)")
    ap.add_argument("--preempt-policy", choices=("youngest", "fewest_pages"),
                    default="youngest",
                    help="victim selection when the pool runs dry mid-decode")
    ap.add_argument("--audit-every", type=int, default=0,
                    help="run the pool/table/index invariant auditor every N "
                         "engine cycles (0 disables; always audits at drain "
                         "when enabled)")
    ap.add_argument("--spec-k", type=int, default=1,
                    help="self-speculative decode depth: draft K tokens per "
                         "cycle against the low-bit committed pools, verify "
                         "in one batched full-fidelity pass (>1 enables; "
                         "docs/SERVING.md §11)")
    ap.add_argument("--spec-bits", type=int, default=None,
                    help="draft-path read precision in bits (default: "
                         "min(2, kv_bits); must be <= kv_bits)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request TTL on the engine clock; overdue "
                         "requests retire as EXPIRED")
    ap.add_argument("--strict", action="store_true",
                    help="raise on unadmittable submissions instead of "
                         "retiring them as REJECTED")
    ap.add_argument("--async-runtime", action="store_true",
                    help="overlapped decode runtime: no per-cycle host sync "
                         "(bounded in-flight window + background completion "
                         "thread); bitwise-identical to the sync cycle "
                         "(docs/SERVING.md §13)")
    ap.add_argument("--async-window", type=int, default=2, metavar="W",
                    help="in-flight decode steps before the host consumes "
                         "the oldest (higher = more overlap, more lag "
                         "discovering retirement)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace_event JSON here (open in "
                         "Perfetto) plus a .jsonl sibling with the raw "
                         "structured events (docs/OBSERVABILITY.md)")
    ap.add_argument("--metrics-every", type=int, default=0, metavar="N",
                    help="print the Prometheus text exposition of the "
                         "metrics registry every N engine cycles (0 off)")
    args = ap.parse_args()
    if args.arch is None:
        if args.family is None:
            ap.error("one of --arch / --family is required")
        args.arch = FAMILY_ARCHS[args.family]

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.with_(kv_bits=args.kv_bits)
    engine = build_engine(
        cfg, slots=args.slots, max_seq=args.max_seq,
        paged=False if args.dense else None, n_pages=args.pages,
        splitkv=args.splitkv, share_prefix=not args.no_prefix_sharing,
        reserve_policy=args.reserve_policy,
        expected_quantile=args.expected_quantile,
        preempt_policy=args.preempt_policy,
        audit_every=args.audit_every, strict=args.strict,
        spec_k=args.spec_k, spec_bits=args.spec_bits,
        async_runtime=args.async_runtime, async_window=args.async_window,
        trace=args.trace_out is not None,
        metrics_every=args.metrics_every,
    )
    print(f"[serve] engine mode: {'paged' if engine.paged else 'exact-length shim'}"
          + (f", pool={engine.n_pages} pages "
             f"({engine.kv_page_bytes} B/page)" if engine.paged else ""))

    rng = np.random.default_rng(0)
    sharing_demo = (
        engine.paged and not args.no_prefix_sharing
        and args.shared_prefix_len > 0
    )
    shared_len = min(args.shared_prefix_len, args.prompt_len)
    prefix = rng.integers(0, cfg.vocab, shared_len).astype(np.int32)
    for uid in range(args.requests):
        tail = rng.integers(
            0, cfg.vocab, args.prompt_len - shared_len
        ).astype(np.int32)
        # sharing demo: stagger completions (real traffic never retires in
        # lockstep) so request lifetimes overlap and the prefix index keeps
        # live donors — pages are discoverable only while a holder is
        # resident.  Without sharing, keep the legacy fixed --max-new.
        engine.submit(Request(
            uid=uid,
            prompt=np.concatenate([prefix, tail]),
            max_new_tokens=args.max_new + (uid % 3 if sharing_demo else 0),
            deadline_s=args.deadline_s,
        ))
    stats = engine.run()
    print(f"[serve] {stats}")
    phase = stats.get("phase_s", {})
    cyc = phase.get("cycle", 0.0)
    print(
        "[serve] latency: "
        f"ttft_p50={stats['ttft_p50_ms']:.2f}ms"
        f" ttft_p99={stats['ttft_p99_ms']:.2f}ms"
        f" tpot_p50={stats['tpot_p50_ms']:.3f}ms"
        f" tpot_p99={stats['tpot_p99_ms']:.3f}ms"
        f" queue_wait_p50={stats['queue_wait_p50_ms']:.2f}ms"
    )
    breakdown = " ".join(
        f"{k}={v:.3f}s({v / cyc:.0%})" if cyc > 0 else f"{k}={v:.3f}s"
        for k, v in sorted(phase.items()) if k != "cycle"
    )
    print(
        f"[serve] phases: cycle={cyc:.3f}s {breakdown} "
        f"host_stall={stats['host_stall_fraction']:.1%}"
    )
    if stats.get("preempted"):
        print(
            f"[serve] pressure: preempted={stats['preempted']}"
            f" preempt_remat_tokens={stats['preempt_remat_tokens']}"
            f" audits={stats['audits']}"
        )
    if args.spec_k > 1:
        print(
            f"[serve] speculative: k={args.spec_k}"
            f" accept_rate={stats.get('spec_accept_rate', 0.0):.3f}"
            f" drafted={stats.get('spec_draft_tokens', 0)}"
            f" accepted={stats.get('spec_accepted_tokens', 0)}"
        )
    if engine.paged and not args.no_prefix_sharing:
        print(
            f"[serve] prefix sharing: hit_rate={stats['prefix_hit_rate']:.3f}"
            f" prefill_tokens_saved={stats['prefill_tokens_saved']}"
            f" cow_copies={stats['cow_copies']}"
        )
    if args.trace_out is not None:
        out = pathlib.Path(args.trace_out)
        engine.tracer.write_chrome(out)
        jsonl = out.with_suffix(".jsonl")
        engine.tracer.write_jsonl(jsonl)
        print(
            f"[serve] trace: {len(engine.tracer.events)} events -> {out} "
            f"(Chrome trace_event; open in Perfetto), raw -> {jsonl}"
        )


if __name__ == "__main__":
    main()
