"""Share of the prompt tokens admitted in the window that the page pool's
prefix cache served (pool.stats tokens_skipped, per admission)."""


def read(ctx):
    w0, w1 = ctx["out"].window
    adm = [r for r in ctx["out"].records
           if r.admitted_at is not None and w0 <= r.admitted_at < w1]
    total = sum(r.prompt_len for r in adm)
    return 100.0 * sum(r.shared for r in adm) / total if total else None
