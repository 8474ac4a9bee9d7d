"""Host time per decode step with nothing queued on the device, in ms: the
mean, over the ``serve.cycle`` spans of the traced window that hold a
``serve.decode_dispatch``, of the cycle's duration less its
``serve.device_wait`` and ``serve.prefill`` time (``bench/spans.py``)."""
from bench import spans


def read(run):
    sp = spans.for_run(run)
    cycles = sp.decode_cycles() if sp else []
    if not cycles:
        return None
    host = sum(c.span.dur - c.time("serve.device_wait") - c.time("serve.prefill")
               for c in cycles)
    return 1e-6 * host / len(cycles)
