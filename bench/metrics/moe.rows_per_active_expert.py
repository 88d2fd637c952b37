"""Rows each held expert computed per call, over the window: the window
difference of the engine's held-expert rows over that of held experts
with a row (per layer and program call). This load sets the expert
kernel's FLOP:byte ratio. None where the program keeps no expert
counters."""
from harness.moe_mla import counters_between


def read(ctx):
    got = counters_between(*ctx["out"].window)
    if got is None or not got[1]:
        return None
    return got[0] / got[1]
