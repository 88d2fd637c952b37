"""Most pages in use after any engine step in the window, as a share of the
pool's usable pages."""


def read(ctx):
    w0, w1 = ctx["out"].window
    used = [u for t, u in ctx["out"].pool_used if w0 <= t < w1]
    return 100.0 * max(used) / ctx["n_pages"] if used else None
