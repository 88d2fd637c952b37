"""Share of the traced stretch, less the client's sleeps (the base of
device.idle_share), in which the device sat idle while the engine obtained
a program: inside a ``program.obtain`` span, the first call of an
executable (trace, lowering, then a compile or a persistent-cache load).
None where the trace holds no engine spans."""
from harness import attribution


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.get("prog_spans"):
        return None
    return attribution.idle_share_in(t, ("program.obtain",))
