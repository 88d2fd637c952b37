"""Whole-step model FLOP/s utilization over the traced stretch: model FLOPs
of every prefill and decode token processed there (from shapes, by the
benchmark's flops functions) over (stretch x the chip's bf16 peak)."""
from harness.accounting import decode_positions, prefill_spans


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None:
        return None
    f, cfg = ctx["flops"], ctx["cfg"]
    t0, t1 = tr["host_window"]
    recs = ctx["out"].records
    flops = sum(f.token_flops(cfg, p + 1) for p in decode_positions(
        recs, t0, t1))
    flops += sum(share * f.span_flops(cfg, a, b)
                 for a, b, share in prefill_spans(recs, t0, t1))
    return 100.0 * flops / (tr["window_s"] * peaks["bf16_flops"])
