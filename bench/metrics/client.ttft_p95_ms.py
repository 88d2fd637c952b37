"""95th percentile, over every request due in the window, of the time from
its due time to the client seeing its first token; a request that never
got one counts with the time up to the drain limit."""
import sys

from harness.stats import percentile, t_cut, ttft_samples


def read(ctx):
    v = ttft_samples(ctx["counted"], t_cut(ctx["out"]))
    print(f"ttft: {len(v)} samples", file=sys.stderr)
    p = percentile(v, 95)
    return None if p is None else 1e3 * p
