"""Device-busy time of the decode programs (``jit_decode_megastep`` events
of the trace's XLA Modules line) in the traced stretch, per token the
engine drained there (``tokens`` of the ``decode.drain`` spans that end in
it), in microseconds. None where the trace holds no decode program or no
drained token."""
from harness import attribution


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.get("modules") or not t.get("prog_spans"):
        return None
    lo, hi = t["lo"], t["hi"]
    toks = sum(attrs.get("tokens", 0) for n, a, b, _, _, attrs
               in t["prog_spans"] if n == "decode.drain" and lo <= b <= hi)
    dev = attribution.module_busy(t, attribution.DECODE)
    if not toks or not dev:
        return None
    return dev * 1e-3 / toks
