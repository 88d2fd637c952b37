"""Three-term roofline from compiled dry-run artifacts (TPU v5e targets).

    compute term    = HLO_FLOPs / peak_FLOPs            (per-chip program)
    memory term     = HLO_bytes / HBM_bw
    collective term = wire_bytes / link_bw

``cost_analysis``/``memory_analysis`` describe the *per-device* SPMD program,
so no division by chip count is applied. Collective bytes are parsed from the
optimized HLO text with ring-model wire coefficients per op kind.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator."""
    bf16_flops: float      # FLOP/s
    hbm_bw: float          # bytes/s
    ici_link_bw: float     # bytes/s per link
    source: str


# Keyed by ``jax.Device.device_kind`` (a v5e reports "TPU v5 lite"). A kind
# missing here is an error (``peaks``), never a silent default.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bw=819e9,
        # 1,600 Gbit/s of interchip interconnect over 4 links
        ici_link_bw=50e9,
        source='Google Cloud documentation, "TPU v5e" (system architecture)'),
}

# the chip the dry-run pods and the analytic explorer price for
DEFAULT_TARGET = "TPU v5 lite"


def peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add the chip to roofline.PEAKS)") from None


PEAK_FLOPS = PEAKS[DEFAULT_TARGET].bf16_flops
HBM_BW = PEAKS[DEFAULT_TARGET].hbm_bw
ICI_BW = PEAKS[DEFAULT_TARGET].ici_link_bw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tuple": 0, "token": 0, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")
# ring-model wire bytes per device, as multiple of the parsed payload bytes
_WIRE_COEF = {
    "all-gather": 1.0,        # receives the full result
    "all-reduce": 2.0,        # reduce-scatter + all-gather
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "ragged-all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(type_str: str) -> int:
    m = _SHAPE_RE.match(type_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes by collective kind, from optimized HLO text.

    For each collective instruction we take the larger of (result bytes,
    summed operand bytes) as the payload — correct for both gather-like
    (result larger) and scatter-like (operands larger) ops — then apply the
    ring coefficient.
    """
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)", line)
        if not m:
            continue
        result_type, opname = m.groups()
        kind = None
        for c in _COLLECTIVES:
            if opname == c or opname.startswith(c + "."):
                kind = c
                break
        if kind is None:
            continue
        if kind == "all-to-all" and "ragged-all-to-all" in line:
            kind = "ragged-all-to-all"
        # result bytes (may be a tuple type)
        res_bytes = sum(_shape_bytes(t) for t in
                        re.findall(r"\w+\[[\d,]*\]", result_type))
        # operand bytes: parse typed operands inside the call parens
        paren = line[line.find("(", line.find(opname)):]
        op_bytes = sum(_shape_bytes(t) for t in
                       re.findall(r"\w+\[[\d,]*\]", paren))
        payload = max(res_bytes, op_bytes)
        # XLA *CPU* promotes bf16 all-reduces to f32 (AllReducePromotion:
        # `to_apply=%...promoted`); TPU reduces bf16 natively. Count the
        # wire at the pre-promotion dtype so the target-hardware roofline
        # is not inflated 2x by a host-backend artifact.
        if kind == "all-reduce" and "promoted" in line:
            payload *= 0.5
        out[kind] = out.get(kind, 0.0) + _WIRE_COEF[kind] * payload
    return out


@dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops_per_chip: float
    hlo_flops: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — remat/redundancy waste detector."""
        return self.model_flops_per_chip / max(self.hlo_flops, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """useful-compute-time / bound-time: the score."""
        useful_s = self.model_flops_per_chip / PEAK_FLOPS
        return useful_s / max(self.bound_s, 1e-30)


def terms_from_artifact(art: dict, model_flops_total: float,
                        n_chips: int) -> RooflineTerms:
    wire = sum(art.get("collectives", {}).values())
    return RooflineTerms(
        compute_s=art["flops"] / PEAK_FLOPS,
        memory_s=art["bytes_accessed"] / HBM_BW,
        collective_s=wire / ICI_BW,
        model_flops_per_chip=model_flops_total / n_chips,
        hlo_flops=art["flops"],
    )


# ------------------------------------------------ analytic model FLOPs ----

def model_flops(cfg, shape, knobs=None) -> float:
    """Analytic useful FLOPs for one step of a cell (whole cluster).

    Train: 6·N_active·tokens + 3·attention; prefill: 2·N_active·tokens +
    attention; decode: 2·N_active·B + decode attention reads.
    """
    from repro.configs.base import ATTN, LOCAL_ATTN, MAMBA, SHARED_ATTN
    from repro.approx.knobs import PRECISE, keep_groups
    knobs = knobs or PRECISE
    n_total = cfg.param_count()
    # active params: MoE uses top_k of n_experts expert MLPs
    n_active = n_total
    if cfg.moe is not None:
        m = cfg.moe
        k = knobs.topk_override or m.top_k
        # a share computes its held experts' fraction of the picks
        active = k * m.n_held / m.n_experts
        expert_p = (m.n_held - active) * 3 * cfg.d_model * m.d_expert
        n_active = n_total - (cfg.n_layers - cfg.n_dense_layers) * expert_p
    # embedding gather is not a matmul; unembed matmul counted separately
    n_active -= cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    keep = keep_groups(cfg.n_groups, knobs.layer_skip)
    layer_frac = len(keep) / cfg.n_groups

    B = shape.global_batch
    if knobs.token_drop and shape.kind == "train":
        B = max(1, int(B * (1.0 - knobs.token_drop)))
    S = shape.seq_len
    if shape.kind == "decode":
        tokens = B
        kv_len = S
    else:
        tokens = B * S
        kv_len = S / 2.0            # causal average

    # attention einsum flops per token: 4 * kv * q_dim per attn layer
    attn = 0.0
    for kind in cfg.kinds():
        if kind in (ATTN, SHARED_ATTN):
            kv = kv_len
        elif kind == LOCAL_ATTN:
            kv = min(cfg.window, kv_len) if shape.kind == "decode" \
                else min(cfg.window, S) / 2.0 + cfg.window / 2.0
            kv = min(kv, kv_len)
        else:
            continue
        if knobs.kv_keep_stride > 1 and shape.kind != "decode":
            kv = kv / knobs.kv_keep_stride
        attn += 4.0 * kv * cfg.q_dim
    attn *= tokens * layer_frac
    if cfg.family == "encdec" and shape.kind != "decode":
        # encoder self-attn + decoder cross-attn
        attn += (cfg.n_encoder_layers * 4.0 * cfg.encoder_seq * cfg.q_dim
                 * B * cfg.encoder_seq)
        attn += cfg.n_layers * 4.0 * cfg.encoder_seq * cfg.q_dim * tokens

    # ssd flops per token per mamba layer: intra-chunk ~2*Q*di + state 4*di*N
    ssd = 0.0
    if cfg.ssm is not None:
        di = cfg.ssm.expand * cfg.d_model
        q = cfg.ssm.chunk if shape.kind != "decode" else 1
        per_tok = 2.0 * q * di + 6.0 * di * cfg.ssm.d_state
        n_mamba = sum(1 for k in cfg.kinds() if k == MAMBA)
        ssd = per_tok * n_mamba * tokens * layer_frac

    matmul = 2.0 * n_active * tokens * layer_frac \
        + 2.0 * cfg.vocab_size * cfg.d_model * tokens  # unembed/logits
    if shape.kind == "decode":
        fwd = matmul + attn + ssd
        return fwd
    if shape.kind == "prefill":
        return matmul + attn + ssd
    return 3.0 * (matmul + attn + ssd)      # fwd + 2x bwd


def admission_terms(cfg, chunk_len: int, kv_len: int, *, n_shards: int = 1,
                    kv_quant: bool = False):
    """Per-DEVICE roofline terms of ONE admission chunk's attention.

    Sums ``kernels.ring_attention``'s per-device cost model over the
    config's attention layers (local layers clamp the visible context to
    their window) and prices it against the chip constants. ``n_shards`` is
    the ring plan's shard count (1 = unsharded): the admission compute/HBM
    terms divide by it, which is exactly what the arbiter's pressure
    attribution for the admission axis should see on a mesh. Returns a dict
    with ``flops_per_device`` / ``hbm_bytes_per_device`` / ``compute_s`` /
    ``memory_s``."""
    from repro.configs.base import ATTN, LOCAL_ATTN, SHARED_ATTN
    from repro.kernels.ring_attention import (sharded_prefill_attn_flops,
                                              sharded_prefill_hbm_bytes)
    hd = cfg.resolved_head_dim
    kv_bytes = 1 if kv_quant else 4
    flops = bytes_ = 0.0
    for kind in cfg.kinds():
        if kind in (ATTN, SHARED_ATTN):
            kv = kv_len
        elif kind == LOCAL_ATTN:
            kv = min(cfg.window + chunk_len, kv_len)
        else:
            continue
        flops += sharded_prefill_attn_flops(chunk_len, kv, cfg.n_heads, hd,
                                            n_shards=n_shards)
        bytes_ += sharded_prefill_hbm_bytes(chunk_len, kv, cfg.n_kv_heads,
                                            hd, n_shards=n_shards,
                                            n_heads=cfg.n_heads,
                                            kv_bytes=kv_bytes)
    return {"flops_per_device": flops, "hbm_bytes_per_device": bytes_,
            "compute_s": flops / PEAK_FLOPS, "memory_s": bytes_ / HBM_BW}


def decode_min_bytes(cfg, shape, n_chips: int, kv_quant: bool = False):
    """Kernel-adjusted lower bound on per-chip decode memory traffic: weights
    + KV/SSM state read once per token step (what the fused Pallas
    flash-decode path achieves on TPU — the HLO term additionally counts the
    softmax-chain traffic that stays in VMEM on hardware)."""
    from repro.configs.base import ATTN, LOCAL_ATTN, MAMBA, SHARED_ATTN
    params_b = cfg.param_count() * 2.0
    kv_bytes = 1 if kv_quant else 2
    cache_b = 0.0
    for kind in (ATTN,) * cfg.n_dense_layers + cfg.kinds():
        if kind in (ATTN, SHARED_ATTN):
            cache_b += cfg.cache_width * kv_bytes * shape.seq_len
        elif kind == LOCAL_ATTN:
            cache_b += cfg.cache_width * kv_bytes * min(cfg.window,
                                                       shape.seq_len)
        elif kind == MAMBA and cfg.ssm is not None:
            di = cfg.ssm.expand * cfg.d_model
            nh = di // cfg.ssm.head_dim
            cache_b += nh * cfg.ssm.head_dim * cfg.ssm.d_state * 4
    cache_b *= shape.global_batch
    return (params_b + cache_b) / n_chips
