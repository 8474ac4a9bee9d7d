"""Whole decode step's share of the chip's bf16 peak in the ``mla_moe``
cells, in %: the model FLOPs of the tokens decoded in the traced window
(every matmul of MLA, the dense MLP, the routers, the shared experts and
the sliced LM head per token; the held experts for exactly the pairs the
program counted, ``moe_pairs_here`` on the decode cycles' ``serve.advance``
spans; latent attention over each row's own context;
``counts_mla_moe.decode_flops``) over the device time of the decode
programs times the peak (``peaks.py``).  None where the program counts no
routed pairs."""
from bench import counts_mla_moe as counts
from bench import spans


def read(run):
    progs = run.programs_with("paged_bitdecode")
    sp = spans.for_run(run)
    if not progs or sp is None:
        return None
    stats = [s.stats for c in sp.decode_cycles() for s in c.inner
             if s.name == "serve.advance" and "moe_pairs_here" in s.stats]
    if not stats:
        return None
    pairs = sum(int(s["moe_pairs_here"]) for s in stats)
    ctx = [c for step in run.decode_contexts for c in step]
    t = sum(p.dur for p in progs) * 1e-9
    return 100.0 * counts.decode_flops(run.conf, ctx, pairs) / (t * run.peaks["flops_bf16"])
