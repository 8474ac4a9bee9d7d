"""Share of the prefill programs' computed tokens that admitted requests
asked for, in %: over the ``serve.prefill`` spans of the traced window,
the sum of their ``tokens`` stat (the admitted rows' suffix tokens) over
the sum of their ``computed`` stat (slots x bucket length); None when the
window holds no prefill (``bench/spans.py``)."""
from bench import spans


def read(run):
    sp = spans.for_run(run)
    pre = sp.named("serve.prefill") if sp else []
    computed = sum(s.stats.get("computed", 0) for s in pre)
    if not computed:
        return None
    return 100.0 * sum(s.stats.get("tokens", 0) for s in pre) / computed
