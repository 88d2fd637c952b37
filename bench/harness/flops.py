"""Operations and bytes from shapes, kept with the benchmark so that a change
to the program cannot change the yardstick.

Model FLOPs count one multiply-add as two operations and only the work the
model needs for a token: projections, the attention over its context (or
the state update), and the unembedding. The embedding lookup, masked rows
and recomputation are not counted.
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    """Sizes by role, from a bench configuration file."""
    if cfg["family"] == "dense":
        d = cfg["hidden_size"]
        return dict(family="dense", d=d, L=cfg["num_hidden_layers"],
                    H=cfg["num_attention_heads"],
                    G=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                    ff=cfg["intermediate_size"], V=cfg["vocab_size"])
    if cfg["family"] == "ssm":
        s = cfg["ssm_cfg"]
        d = cfg["d_model"]
        di = s["expand"] * d
        return dict(family="ssm", d=d, L=cfg["n_layer"], di=di,
                    nh=di // s["headdim"], N=s["d_state"], W=s["d_conv"],
                    V=cfg["vocab_size"])
    raise ValueError(f"unknown family {cfg['family']!r}")


def token_flops(cfg: dict, context: int) -> float:
    """Model FLOPs of one token at ``context`` positions of history
    (the token itself included)."""
    m = dims(cfg)
    d, L = m["d"], m["L"]
    unembed = 2 * d * m["V"]
    if m["family"] == "dense":
        q, kv = m["H"] * m["hd"], m["G"] * m["hd"]
        proj = 2 * (d * q + 2 * d * kv + q * d + 3 * d * m["ff"])
        attn = 2 * 2 * context * m["H"] * m["hd"]        # QK^T and PV
        return L * (proj + attn) + unembed
    di, N, nh = m["di"], m["N"], m["nh"]
    proj = 2 * (d * (2 * di + 2 * N + nh) + di * d)
    conv = 2 * (di + 2 * N) * m["W"]
    # recurrent SSD step: decay and input outer product (4 di N), readout
    # through C (2 di N), skip and gate (di terms, small)
    ssd = 6 * di * N
    return L * (proj + conv + ssd) + unembed


def span_flops(cfg: dict, start: int, end: int) -> float:
    """Model FLOPs of the tokens at positions [start, end), in closed form
    over their contexts (position p sees p + 1 entries)."""
    n = max(end - start, 0)
    if n == 0:
        return 0.0
    base = token_flops(cfg, 0)
    m = dims(cfg)
    if m["family"] != "dense":
        return n * base
    ctx_sum = (end * (end + 1) - start * (start + 1)) / 2
    return n * base + m["L"] * 4 * m["H"] * m["hd"] * ctx_sum


# ---- paged attention (the fused decode kernel), copied from the program's
# byte model (kernels/paged_attention.py page_hbm_bytes / decode_hbm_bytes)
# so that the program may change but this yardstick may not.

def page_hbm_bytes(page_size: int, n_kv_heads: int, head_dim: int, *,
                   kv_bytes: int) -> int:
    """HBM bytes one live page streams: K and V entries plus its int32
    position row."""
    return 2 * page_size * n_kv_heads * head_dim * kv_bytes + 4 * page_size


def paged_decode_token_bytes(cfg: dict, position: int, page_size: int,
                             kv_bytes: int = 2, q_bytes: int = 2) -> int:
    """Bytes the kernel needs, over all layers, for one decode token whose
    query sits at ``position``: every page holding positions 0..position
    once, and the token's query and output vectors."""
    m = dims(cfg)
    live = position // page_size + 1
    per_layer = (live * page_hbm_bytes(page_size, m["G"], m["hd"],
                                       kv_bytes=kv_bytes)
                 + 2 * m["H"] * m["hd"] * q_bytes)
    return m["L"] * per_layer


def paged_decode_token_flops(cfg: dict, position: int) -> float:
    """Attention FLOPs of one decode token at ``position``, all layers."""
    m = dims(cfg)
    return m["L"] * 4 * (position + 1) * m["H"] * m["hd"]


def param_bytes(cfg: dict, bytes_per: int = 2) -> int:
    """Bytes of the weights (tied embedding counted once)."""
    m = dims(cfg)
    d, L = m["d"], m["L"]
    if m["family"] == "dense":
        q, kv = m["H"] * m["hd"], m["G"] * m["hd"]
        per = d * q + 2 * d * kv + q * d + 3 * d * m["ff"] + 2 * d
    else:
        di, N, nh, W = m["di"], m["N"], m["nh"], m["W"]
        per = (d * (2 * di + 2 * N + nh) + (di + 2 * N) * W + 3 * nh
               + di * d + di + d)
    return bytes_per * (L * per + m["V"] * d + d)
