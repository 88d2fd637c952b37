"""Share of the device's busy time in the traced stretch spent in the
admission program (``jit_prefill_chunk`` events of the trace's XLA Modules
line). None where no program of the trace carries a step program's name."""
from harness import attribution
from harness import trace as tr


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.get("modules"):
        return None
    pre = attribution.module_busy(t, attribution.PREFILL)
    dec = attribution.module_busy(t, attribution.DECODE)
    busy = tr.length(t["busy"])
    if not (pre or dec) or busy <= 0:
        return None
    return 100.0 * pre / busy
