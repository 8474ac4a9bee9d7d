"""Output tokens per second of the window, in tokens/s: every token that a
``ServeEngine.step()`` returning inside the window made visible, over the
window's seconds (its start to the last step's return)."""


def read(run):
    return sum(s.tokens for s in run.steps) / (run.t1 - run.t0)
