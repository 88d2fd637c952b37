"""The fused paged-attention decode kernel's share of its roofline: the
least time the chip needs for the kernel's work on the tokens emitted in
the traced stretch (bytes of every live page each token reads, and its
FLOPs, by the benchmark's copy of the byte model) over the device time of
the kernel's events there. None where the kernel did not run."""
from harness.accounting import decode_positions
from harness.trace import kernel_seconds

# Pallas kernels run as XLA custom calls ("tpu_custom_call"); on a precise
# phi4 cell the only one is the paged-attention decode kernel, whose trace
# ops read "%paged_attention_impl.N = ... custom_call_target=tpu_custom_call"
PATTERN = r"tpu_custom_call"


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None:
        return None
    secs, n = kernel_seconds(tr["ops"], PATTERN, tr["lo"], tr["hi"])
    if not n or secs <= 0:
        return None
    f, cfg, P = ctx["flops"], ctx["cfg"], ctx["page_size"]
    t0, t1 = tr["host_window"]
    pos = list(decode_positions(ctx["out"].records, t0, t1))
    nbytes = sum(f.paged_decode_token_bytes(cfg, p, P) for p in pos)
    flops = sum(f.paged_decode_token_flops(cfg, p) for p in pos)
    need = max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])
    return 100.0 * need / secs
