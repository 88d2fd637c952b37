"""Set-up time: process start to the first due request (imports, weights on
the device, engine build, warm-up, and compiles where the cache is cold)."""


def read(ctx):
    return ctx["setup_s"]
