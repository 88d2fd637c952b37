"""Output tokens the client saw inside the window, over the window."""
from harness.stats import rate


def read(ctx):
    w0, w1 = ctx["out"].window
    n = sum(1 for r in ctx["out"].records for t in r.stamps if w0 <= t < w1)
    return rate(n, w1 - w0)
