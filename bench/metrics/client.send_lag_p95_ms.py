"""How late the client sent requests: 95th percentile of sent - due."""
from harness.stats import percentile


def read(ctx):
    p = percentile([r.sent - r.due for r in ctx["counted"]], 95)
    return None if p is None else 1e3 * p
