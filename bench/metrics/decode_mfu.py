"""Whole decode step's share of the chip's bf16 peak, in %: the model
FLOPs of the tokens decoded in the traced window (every matmul and the LM
head per token, attention over each row's real context;
``counts.decode_flops``) over the device time of the decode programs
times the peak (``peaks.py``)."""
from bench import counts


def read(run):
    progs = run.programs_with("paged_bitdecode")
    if not progs:
        return None
    ctx = [c for step in run.decode_contexts for c in step]
    t = sum(p.dur for p in progs) * 1e-9
    return 100.0 * counts.decode_flops(run.conf, ctx) / (t * run.peaks["flops_bf16"])
