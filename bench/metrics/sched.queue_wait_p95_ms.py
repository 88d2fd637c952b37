"""Scheduler queue wait: 95th percentile of the engine's first-chunk stamp
(Request.t_admit_start) minus the due time."""
from harness.stats import percentile


def read(ctx):
    p = percentile([r.admit_start - r.due for r in ctx["counted"]
                    if r.admit_start is not None], 95)
    return None if p is None else 1e3 * p
