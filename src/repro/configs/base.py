"""Config dataclasses for architectures, shapes, and meshes.

Every assigned architecture is expressed as a ``ModelConfig``; the four
assigned input shapes are ``ShapeConfig`` instances in ``SHAPES``. Reduced
("smoke") configs reuse the same family logic at toy scale so every arch can
run a real forward/train step on CPU.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

# Layer kinds used to build the per-layer pattern of a model. The decoder
# stack scans over *groups* of layers; a group is one period of the pattern.
ATTN = "attn"            # full (global) self-attention
LOCAL_ATTN = "local"     # sliding-window self-attention
MAMBA = "mamba"          # Mamba2 SSD mixer
SHARED_ATTN = "shared"   # zamba2-style shared-weight attention block


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int                   # routed experts the router scores
    top_k: int
    d_expert: int                    # width of one routed expert's SwiGLU
    # capacity factor of the expert-parallel dispatch (tokens per expert per
    # batch*seq); the single-device layer is dropless
    capacity_factor: float = 1.25
    # routed experts this chip holds: (first, count); count 0 = all of them.
    # Picks on the others add nothing here (they live on other chips)
    held: Tuple[int, int] = (0, 0)
    d_shared: int = 0                # width of the shared experts' SwiGLU
    scoring: str = "softmax"         # softmax | sigmoid
    score_bias: bool = False         # selection adds a learned per-expert
                                     # bias (DeepSeek-V3 noaux_tc)
    scale: float = 1.0               # routed_scaling_factor (picked
                                     # weights renormalised to sum 1 first)

    @property
    def held_range(self) -> Tuple[int, int]:
        """[lo, hi) of the routed experts held here."""
        lo, n = self.held
        return (lo, lo + (n or self.n_experts))

    @property
    def n_held(self) -> int:
        lo, hi = self.held_range
        return hi - lo


@dataclass(frozen=True)
class SSMConfig:
    d_state: int
    expand: int = 2
    head_dim: int = 64
    conv_width: int = 4
    chunk: int = 128  # SSD chunk length


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    # layer pattern: tuple of layer kinds, one *period*; tiled to n_layers.
    pattern: Tuple[str, ...] = (ATTN,)
    window: int = 0                  # sliding window size for LOCAL_ATTN
    attn_softcap: float = 0.0        # gemma2-style attention logit softcap
    final_softcap: float = 0.0       # gemma2-style final logit softcap
    rope_theta: float = 10_000.0
    # latent attention (MLA, DeepSeek-V2): > 0 caches one ``kv_lora_rank``
    # latent plus one shared ``rope_dim`` rotary key per token; heads take
    # ``head_dim`` nope dims + ``rope_dim`` rope dims for q/k, ``head_dim``
    # for v (q_lora_rank absent: q comes straight from x)
    kv_lora_rank: int = 0
    rope_dim: int = 0
    # leading dense layers (first_k_dense_replace): SwiGLU of width d_ff,
    # counted in n_layers, outside the scanned groups
    n_dense_layers: int = 0
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # enc-dec (whisper): encoder layers share d_model/heads/d_ff
    n_encoder_layers: int = 0
    encoder_seq: int = 0             # fixed stub frame-embedding length
    # vlm (paligemma): number of prefix patch-embedding tokens (stub frontend)
    n_prefix_tokens: int = 0
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    # does full attention appear in the pattern? (long_500k gating)
    sub_quadratic: bool = False
    max_position: int = 1 << 20

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.resolved_head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.resolved_head_dim

    @property
    def n_groups(self) -> int:
        n = self.n_layers - self.n_dense_layers
        assert n % len(self.pattern) == 0, (
            f"{self.name}: {n} scanned layers not divisible by "
            f"pattern period {len(self.pattern)}")
        return n // len(self.pattern)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def cache_width(self) -> int:
        """Cache entries per token per attention layer: K and V of every KV
        head, or MLA's one latent plus its shared rotary key."""
        if self.mla:
            return self.kv_lora_rank + self.rope_dim
        return 2 * self.kv_dim

    def kinds(self) -> Tuple[str, ...]:
        """Per-layer kind sequence of the scanned stack (pattern tiled over
        the layers after the leading dense ones)."""
        return tuple(self.pattern[i % len(self.pattern)]
                     for i in range(self.n_layers - self.n_dense_layers))

    def param_count(self) -> int:
        """Analytic parameter count (matches init exactly; asserted in tests)."""
        d, hd = self.d_model, self.resolved_head_dim
        n = 0
        n += self.vocab_size * d                       # embedding
        if not self.tie_embeddings:
            n += self.vocab_size * d
        per_kind = {}
        attn_p = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if self.mla:
            r, hr = self.kv_lora_rank, self.rope_dim
            attn_p = (d * self.n_heads * (hd + hr) + d * (r + hr) + r
                      + 2 * r * self.q_dim + self.q_dim * d)
        dense_p = 3 * d * self.d_ff                    # SwiGLU: gate/up/down
        mlp_p = dense_p
        if self.moe is not None:
            m = self.moe
            mlp_p = (m.n_held * 3 * d * m.d_expert + d * m.n_experts
                     + 3 * d * m.d_shared
                     + (m.n_experts if m.score_bias else 0))
        n += self.n_dense_layers * (attn_p + dense_p + 2 * d)
        per_kind[ATTN] = attn_p + mlp_p + 2 * d
        per_kind[LOCAL_ATTN] = per_kind[ATTN]
        if self.ssm is not None:
            di = self.ssm.expand * d
            nh = di // self.ssm.head_dim
            # in_proj: [d, 2*di + 2*d_state + nh] (z, x, B, C, dt) with n_groups=1
            in_p = d * (2 * di + 2 * self.ssm.d_state + nh)
            conv_p = (di + 2 * self.ssm.d_state) * self.ssm.conv_width
            extra = nh * 3                             # A_log, D, dt_bias
            out_p = di * d + di                        # out_proj + gate norm
            # Mamba blocks carry no MLP (mamba2/zamba2 style); d_ff belongs to
            # attention / shared blocks only.
            per_kind[MAMBA] = in_p + conv_p + extra + out_p + d
        shared = 0
        if SHARED_ATTN in self.pattern:
            shared = attn_p + 3 * d * self.d_ff + 2 * d
        for k in self.kinds():
            if k == SHARED_ATTN:
                continue                               # counted once below
            n += per_kind[k]
        n += shared
        n += d                                         # final norm
        if self.n_encoder_layers:
            # encoder: self-attn + MLP blocks; decoder layers add cross-attn
            enc = self.n_encoder_layers * (attn_p + mlp_p + 2 * d) + d
            cross = self.n_layers * (attn_p + d)
            n += enc + cross
        if self.n_prefix_tokens:
            n += 0                                     # stub frontend: no params
        return n


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether an (arch, shape) cell runs; (False, reason) for documented skips."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "pure full-attention arch: 500k decode cache is quadratic-cost to build; skipped per brief (DESIGN.md §5)"
    if shape.name == "long_500k" and cfg.family == "encdec":
        return False, "enc-dec decoder max context << 500k (DESIGN.md §5)"
    return True, ""


def smoke_config(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family config: small dims, few layers/experts, tiny vocab."""
    if cfg.n_kv_heads <= 1:
        smoke_kv = 1                       # preserve MQA
    elif cfg.n_kv_heads < cfg.n_heads:
        smoke_kv = 2                       # preserve GQA
    else:
        smoke_kv = 4                       # preserve MHA
    kw = dict(
        name=cfg.name + "-smoke",
        n_layers=len(cfg.pattern) * min(2, cfg.n_groups),
        d_model=64,
        n_heads=4,
        n_kv_heads=smoke_kv,
        head_dim=16,
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=256,
        window=min(cfg.window, 32) if cfg.window else 0,
        max_position=4096,
    )
    if cfg.moe is not None:
        m = cfg.moe
        held = (0, 0) if not m.held[1] else (0, 4)   # a share: 4 of 8
        kw["moe"] = replace(m, n_experts=8, top_k=min(m.top_k, 3), d_expert=32,
                            held=held, d_shared=32 if m.d_shared else 0)
    if cfg.mla:
        kw.update(kv_lora_rank=32, rope_dim=16)
    if cfg.n_dense_layers:
        kw.update(n_dense_layers=1, n_layers=1 + kw["n_layers"])
    if cfg.ssm is not None:
        kw["ssm"] = SSMConfig(d_state=16, expand=2, head_dim=16, chunk=16)
    if cfg.n_encoder_layers:
        kw["n_encoder_layers"] = 2
        kw["encoder_seq"] = 16
    if cfg.n_prefix_tokens:
        kw["n_prefix_tokens"] = 4
    return replace(cfg, **kw)
