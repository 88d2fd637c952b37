"""Peak device memory in use over the run, as a share of the memory the
device gives the process (memory_stats peak_bytes_in_use / bytes_limit)."""


def read(ctx):
    m = ctx["mem"]
    if not m.get("bytes_limit"):
        return None
    return 100.0 * m["peak_bytes_in_use"] / m["bytes_limit"]
