"""Share of the traced window in which no operation ran on the device, in
%: one minus the union of the device's op intervals over the window
(``trace.busy_s``)."""
from bench import trace


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.trace) / run.trace.window_s)
