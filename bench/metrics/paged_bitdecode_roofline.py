"""``paged_bitdecode``'s share of its roofline, in %: per kernel call (one
layer of one decode step) the larger of its FLOPs over the bf16 peak and
its bytes over the HBM bandwidth, where the bytes are each active row's
own resident pages at the pool's layout, its bf16 residual tail, its query
and output (``counts.paged_decode_call``; idle slots and the walk over the
longest row's pages do not count), summed over the traced window and
divided by the kernel's device time."""
from bench import counts


def read(run):
    evs = run.kernel_events("paged_bitdecode")
    if not evs:
        return None
    layers = run.conf["num_hidden_layers"]
    need = 0.0
    for step in run.decode_contexts:
        flops, nbytes = counts.paged_decode_call(run.conf, step)
        need += layers * max(flops / run.peaks["flops_bf16"],
                             nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / (sum(e.dur for e in evs) * 1e-9)
