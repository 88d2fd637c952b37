"""Work of the latent-attention decode kernel and the grouped expert kernel,
from shapes (kept with the benchmark, like ``flops.py``), and the engine's
expert counters over a stretch of host time.

The counters are the program's: ``ServeEngine.counter_marks`` holds the
running totals of held-expert rows computed and of held experts that got a
row (per layer and program call), stamped on the host clock at the drain
that read them. A stretch's counts are the difference of the totals at its
ends. Where the program keeps no such marks, there is nothing to read.
"""
from __future__ import annotations

import bisect
import gc
import sys
from typing import Optional, Tuple


def latent_entry_bytes(cfg: dict, kv_bytes: int = 2) -> int:
    """Bytes of one cached token of one layer: latent and rotary key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * kv_bytes


def latent_decode_token_bytes(cfg: dict, position: int) -> int:
    """Bytes the latent decode kernel needs, over all layers, for one token
    at ``position``: every live entry (positions 0..position) once."""
    return cfg["num_hidden_layers"] * (position + 1) * latent_entry_bytes(cfg)


def latent_decode_token_flops(cfg: dict, position: int) -> float:
    """FLOPs of one token's absorbed attention over ``position + 1``
    entries, all layers: scores over the whole entry, values over the
    latent, for every head."""
    W = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return (cfg["num_hidden_layers"] * 2 * cfg["num_attention_heads"]
            * (position + 1) * (W + cfg["kv_lora_rank"]))


def expert_bytes(cfg: dict, rows: int, active: int) -> int:
    """Bytes the grouped expert kernel needs: gate, up and down matrices of
    each expert that got a row, once, and each row read and written."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return active * 3 * d * f * 2 + rows * d * 4


def expert_flops(cfg: dict, rows: int) -> float:
    """FLOPs of ``rows`` token rows through one expert's SwiGLU each."""
    return rows * 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _marks():
    mod = sys.modules.get("repro.serve.engine")
    if mod is None:
        return None
    found = [list(m) for o in gc.get_objects()
             if isinstance(o, mod.ServeEngine)
             for m in [getattr(o, "counter_marks", None)] if m]
    return max(found, key=lambda m: m[-1][0]) if found else None


def counters_between(t0: float, t1: float) -> Optional[Tuple[int, int]]:
    """(rows, active) the engine's expert counters gained between host
    times t0 and t1; None where the program keeps no counter marks."""
    marks = _marks()
    if marks is None:
        return None
    times = [m[0] for m in marks]

    def at(t):
        i = bisect.bisect_right(times, t)
        return marks[i - 1][1:] if i else (0, 0)

    (r0, a0), (r1, a1) = at(t0), at(t1)
    return r1 - r0, a1 - a0
