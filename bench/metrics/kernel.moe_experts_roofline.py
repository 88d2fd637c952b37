"""The grouped expert kernel's share of its roofline over the traced
stretch: the least time the chip needs for the expert rows the engine
counted there (each active expert's three matrices read once, each row read
and written; and the rows' FLOPs, by ``harness.moe_mla``), over the device
time of the kernel's events. None where the kernel or the counters are
absent."""
from harness.moe_mla import counters_between, expert_bytes, expert_flops
from harness.trace import kernel_seconds

# the Pallas kernel is named moe_experts ("%moe_experts.N = ...
# custom_call_target=tpu_custom_call"); matched by name, not by the target
PATTERN = r"moe_experts"


def read(ctx):
    tr, peaks, cfg = ctx["trace"], ctx["peaks"], ctx["cfg"]
    if tr is None or peaks is None or "moe_intermediate_size" not in cfg:
        return None
    secs, n = kernel_seconds(tr["ops"], PATTERN, tr["lo"], tr["hi"])
    got = counters_between(*tr["host_window"])
    if not n or secs <= 0 or not got or not got[1]:
        return None
    rows, active = got
    need = max(expert_bytes(cfg, rows, active) / peaks["hbm_bytes_per_s"],
               expert_flops(cfg, rows) / peaks["bf16_flops"])
    return 100.0 * need / secs
