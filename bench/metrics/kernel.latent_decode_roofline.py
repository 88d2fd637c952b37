"""The latent (MLA) paged decode kernel's share of its roofline: the least
time the chip needs for the kernel's work on the tokens emitted in the
traced stretch (every live latent entry each token reads, and its FLOPs,
by ``harness.moe_mla``) over the device time of the kernel's events there.
None where the kernel did not run."""
from harness.accounting import decode_positions
from harness.moe_mla import latent_decode_token_bytes, \
    latent_decode_token_flops
from harness.trace import kernel_seconds

# matched by the kernel's name, not by tpu_custom_call (any Pallas kernel)
PATTERN = r"paged_latent_decode"


def read(ctx):
    tr, peaks, cfg = ctx["trace"], ctx["peaks"], ctx["cfg"]
    if tr is None or peaks is None or "kv_lora_rank" not in cfg:
        return None
    secs, n = kernel_seconds(tr["ops"], PATTERN, tr["lo"], tr["hi"])
    if not n or secs <= 0:
        return None
    t0, t1 = tr["host_window"]
    pos = list(decode_positions(ctx["out"].records, t0, t1))
    nbytes = sum(latent_decode_token_bytes(cfg, p) for p in pos)
    flops = sum(latent_decode_token_flops(cfg, p) for p in pos)
    need = max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])
    return 100.0 * need / secs
