"""95th percentile, over requests due in the window that ask for two tokens
or more, of (last token seen - first token seen) / (tokens - 1): the
streaming speed a user feels, tokens that arrive together averaged over the
request. A request that did not finish counts as if its last token came at
the drain limit."""
import sys

from harness.stats import percentile, t_cut, tpot_samples


def read(ctx):
    v = tpot_samples(ctx["counted"], t_cut(ctx["out"]))
    print(f"tpot: {len(v)} samples", file=sys.stderr)
    p = percentile(v, 95)
    return None if p is None else 1e3 * p
