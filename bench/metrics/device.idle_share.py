"""Share of the traced stretch in which no operation ran on the device,
counting only the time the engine had work: stretches the client spent
asleep waiting for arrivals (the engine empty) are left out."""
from harness import trace as tr_mod


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    lo, hi = tr["lo"], tr["hi"]
    sleep = tr_mod.clip(tr_mod.union(
        [(a, b) for lab, a, b in tr["spans"] if lab == "sleep"]), lo, hi)
    work = tr_mod.subtract([(lo, hi)], sleep)
    span = tr_mod.length(work)
    if span <= 0:
        return None
    busy = tr_mod.length(tr_mod.subtract(tr["busy"], sleep))
    return 100.0 * (1.0 - busy / span)
