"""Admission executables the engine made anew inside the window: the
difference of ``stats['prefill_exe_misses']`` (misses of its bounded LRU)
between the window's edges. None where the counters do not hold it."""


def read(ctx):
    c = ctx["out"].counters
    key = "prefill_exe_misses"
    if key not in c.get("start", {}) or key not in c.get("end", {}):
        return None
    return c["end"][key] - c["start"][key]
