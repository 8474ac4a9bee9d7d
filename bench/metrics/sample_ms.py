"""Time the engine spends on argmax and the finiteness guard over the
logits rows per decode step, in ms: the ``serve.sample`` spans of the
traced window's decode cycles (``serve.cycle`` spans that hold a
``serve.decode_dispatch``), summed and divided by the count of those
cycles (``bench/spans.py``)."""
from bench import spans


def read(run):
    sp = spans.for_run(run)
    cycles = sp.decode_cycles() if sp else []
    if not any(c.has("serve.sample") for c in cycles):
        return None
    return 1e-6 * sum(c.time("serve.sample") for c in cycles) / len(cycles)
