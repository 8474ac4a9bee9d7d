"""The held experts' grouped matmul's share of its roofline in the decode
step, in %: over the decode cycles of the traced window, the larger of the
step's expert FLOPs over the bf16 peak and its expert bytes over the HBM
bandwidth (``counts_mla_moe.expert_step``: the pairs the program counted,
``moe_pairs_here``, through gate, up and down; each held expert with a
pair, ``moe_experts_hit``, read once; the pairs' activations), summed, over
the device time of the grouped-matmul kernel (``%gmm``, megablox) inside
the decode programs (those that ran ``paged_bitdecode``).  None where the
program counts no routed pairs or runs no such kernel."""
from bench import counts_mla_moe as counts
from bench import spans

KERNEL = "gmm"


def read(run):
    sp = spans.for_run(run)
    progs = run.programs_with("paged_bitdecode")
    evs = run.kernel_events(KERNEL)
    if sp is None or not progs or not evs:
        return None
    stats = [s.stats for c in sp.decode_cycles() for s in c.inner
             if s.name == "serve.advance" and "moe_pairs_here" in s.stats]
    if not stats:
        return None
    starts = sorted((p.start, p.end) for p in progs)
    t = sum(e.dur for e in evs if any(a <= e.start <= b for a, b in starts)) * 1e-9
    if t <= 0:
        return None
    need = 0.0
    for s in stats:
        flops, nbytes = counts.expert_step(run.conf, int(s["moe_pairs_here"]),
                                           int(s["moe_experts_hit"]))
        need += max(flops / run.peaks["flops_bf16"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / t
