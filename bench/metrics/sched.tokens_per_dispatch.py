"""Mean effective megastep K over the window: the engine's row_tokens over
row_dispatches (a row in a drain that brought n tokens adds (1, n))."""


def read(ctx):
    c = ctx["out"].counters
    if "start" not in c or "end" not in c:
        return None
    d = c["end"]["row_dispatches"] - c["start"]["row_dispatches"]
    t = c["end"]["row_tokens"] - c["start"]["row_tokens"]
    return t / d if d else None
