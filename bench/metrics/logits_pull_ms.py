"""Time the engine spends pulling the logits rows to the host per decode
step, in ms: the ``serve.logits_pull`` spans of the traced window's decode
cycles (``serve.cycle`` spans that hold a ``serve.decode_dispatch``),
summed and divided by the count of those cycles (``bench/spans.py``)."""
from bench import spans


def read(run):
    sp = spans.for_run(run)
    cycles = sp.decode_cycles() if sp else []
    if not any(c.has("serve.logits_pull") for c in cycles):
        return None
    return 1e-6 * sum(c.time("serve.logits_pull") for c in cycles) / len(cycles)
