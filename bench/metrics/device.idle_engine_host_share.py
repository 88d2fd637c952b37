"""Share of the traced stretch, less the client's sleeps (the base of
device.idle_share), in which the device sat idle while the engine's host
code ran: inside an ``engine.step`` span but outside ``program.obtain``
and the two drains (``decode.drain``, ``admit.drain``), where the host
waits on the device by design. None where the trace holds no engine
spans."""
from harness import attribution


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.get("prog_spans"):
        return None
    return attribution.idle_share_in(
        t, ("engine.step",), ("program.obtain",) + attribution.DRAINS)
