"""Device time per decode program in the closed-loop cell, in ms: the
programs that ran ``paged_bitdecode`` inside the traced window, summed
and divided by their count (``bench/trace.py``)."""


def read(run):
    progs = run.programs_with("paged_bitdecode")
    if not progs:
        return None
    return 1e-6 * sum(p.dur for p in progs) / len(progs)
