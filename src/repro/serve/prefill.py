"""Batched prefill with cache fill: run the full-sequence forward ONCE and
hand the populated KV/SSM caches to incremental decode — the production
serving handoff (vs. feeding prompt tokens through decode steps one by one).

Additive module: reuses the per-kind mixers but emits cache entries as scan
outputs (stacked over layer groups, exactly the decode cache layout).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, LOCAL_ATTN, MAMBA, SHARED_ATTN,
                                ModelConfig)
from repro.approx.knobs import ApproxKnobs, PRECISE
from repro.models import attention as attn_mod
from repro.models import mamba2 as mamba_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models.attention import KVCache
from repro.models.common import apply_rope, rms_norm
from repro.models.lm import logits_fn, scan_layers
from repro.kernels import ref as kref
from repro.kernels import ops as kops


def _attn_block_with_kv(params, h, positions, cfg, kind, knobs, max_len):
    """Attention block that also returns the KVCache entry for decode."""
    hn = rms_norm(h, params["norm_attn"], cfg.norm_eps)
    B, S, _ = hn.shape
    hd = cfg.resolved_head_dim
    k = hn @ params["attn"]["wk"]
    v = hn @ params["attn"]["wv"]
    k = apply_rope(k.reshape(B, S, cfg.n_kv_heads, hd), positions,
                   cfg.rope_theta)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    mode = "window" if kind == LOCAL_ATTN else "causal"
    y = attn_mod.attention(params["attn"], hn, positions, cfg, mode=mode,
                           kv_keep_stride=knobs.kv_keep_stride)
    h = h + y
    hn2 = rms_norm(h, params["norm_mlp"], cfg.norm_eps)
    if "moe" in params:
        y2, _ = moe_mod.moe(params["moe"], hn2, cfg,
                            top_k=knobs.topk_override,
                            precision=knobs.matmul_precision)
    else:
        y2 = mlp_mod.mlp(params["mlp"], hn2,
                         precision=knobs.matmul_precision)
    h = h + y2
    # build the cache entry (ring layout, first S slots filled)
    W = min(cfg.window, max_len) if kind == LOCAL_ATTN else max_len
    kc = jnp.zeros((B, W, cfg.n_kv_heads, hd), k.dtype)
    vc = jnp.zeros_like(kc)
    pos = jnp.full((B, W), -1, jnp.int32)
    n_keep = min(S, W)
    kc = kc.at[:, :n_keep].set(k[:, S - n_keep:])
    vc = vc.at[:, :n_keep].set(v[:, S - n_keep:])
    pos = pos.at[:, :n_keep].set(
        jnp.broadcast_to(jnp.arange(S - n_keep, S), (B, n_keep)))
    cache = KVCache(kc, vc, pos, jnp.asarray(n_keep % W, jnp.int32)
                    if W > n_keep else jnp.asarray(0, jnp.int32))
    return h, cache


def _mamba_block_with_state(params, h, cfg, knobs):
    """Mamba block returning the MambaCache for decode handoff."""
    p = params["mixer"]
    hn = rms_norm(h, params["norm"], cfg.norm_eps)
    B, S, D = hn.shape
    di, nh, n = mamba_mod._dims(cfg)
    mm = kops.matmul(knobs.matmul_precision)
    z = mm(hn, p["in_z"])
    xs_in = mm(hn, p["in_x"])
    bc_in = hn @ p["in_bc"]
    xs, hist_x = mamba_mod._causal_conv(xs_in, p["conv_x"])
    bc, hist_bc = mamba_mod._causal_conv(bc_in, p["conv_bc"])
    dt_raw = hn @ p["in_dt"]
    b, c = jnp.split(bc, 2, axis=-1)
    xs4 = xs.reshape(B, S, nh, cfg.ssm.head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    y, state = kref.ssd_chunked_ref(xs4, dt, a, b, c, chunk=cfg.ssm.chunk,
                                    d_skip=p["d_skip"], return_state=True)
    y = y.reshape(B, S, di)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
    h = h + mm(y, p["out_proj"])
    cache = mamba_mod.MambaCache(conv_x=hist_x, conv_bc=hist_bc, state=state)
    return h, cache


PAD_TOKEN = -1      # pad entry of a paged admission chunk


def pads_exactly(cfg: ModelConfig) -> bool:
    """True when pad rows in a paged admission chunk leave every valid
    row's result and every mapped page as an exact-length chunk would: no
    SSM layer (its state would absorb the pad tokens). Expert layers pad
    exactly: serving runs them dropless, and pad rows take no expert
    rows."""
    return MAMBA not in cfg.pattern


def paged_prefill_chunk(params, tokens, start, caches, slot,
                        cfg: ModelConfig, knobs: ApproxKnobs = PRECISE,
                        dyn_scatter: bool = False, *, mesh=None,
                        use_kernel: Optional[bool] = None,
                        interpret: bool = False):
    """One prompt chunk for ONE slot of the paged engine caches.

    tokens: (1, C); start: traced scalar absolute position; slot: traced
    scalar batch row. The chunk writes straight into the batched page pool
    through the slot's block table — prefix-shared pages are simply already
    mapped. Returns
    (last-valid-token logits (1,V) fp32, advanced caches).

    The chunk may end in pad entries (``PAD_TOKEN``) after n >= 1 valid
    tokens, so one executable of length C serves every shorter chunk too.
    Each pad row repeats the last valid token at its position, so it
    computes a copy of row n-1 over the same keys and stays finite under
    every causal and window mask; its K/V and position writes go to the
    never-read null page, and they take no expert rows. An all-valid chunk
    is the plain computation. The pad rows are exact only where
    ``pads_exactly(cfg)``.

    Returns (logits, counts, caches): ``counts`` (2,) int32 are the chunk's
    expert counters summed over layers (``blocks._serve_ffn``).
    """
    from repro.models.blocks import block_prefill_paged
    B, C = tokens.shape
    valid = tokens[0] >= 0                                    # (C,)
    last = jnp.sum(valid.astype(jnp.int32)) - 1
    rel = jnp.minimum(jnp.arange(C), last)
    h = params["embed"][jnp.take(tokens, rel, axis=1)]
    positions = start + jnp.broadcast_to(rel, (B, C))

    def layer(kind, p, h, cache, i):
        return block_prefill_paged(
            kind, p, h, positions, cache, cfg, knobs, slot=slot, valid=valid,
            layer=i, dyn_scatter=dyn_scatter, mesh=mesh,
            use_kernel=use_kernel, interpret=interpret)

    h, new_caches, counts = scan_layers(params, caches, cfg, h, layer)
    h = jax.lax.dynamic_index_in_dim(h, last, axis=1, keepdims=False)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return logits_fn(params, h, cfg), counts, new_caches


def prefill_with_cache(params, tokens, cfg: ModelConfig, max_len: int,
                       knobs: ApproxKnobs = PRECISE):
    """tokens: (B, S) -> (last-token logits (B,V) fp32, decode caches).

    The returned caches are exactly ``lm.init_caches`` layout with the first
    S positions populated; ``lm.decode_step`` continues from position S.
    """
    h = params["embed"][tokens]
    B, S, D = h.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    shared = params.get("shared")

    def group_body(h, group_params):
        caches = []
        for j, kind in enumerate(cfg.pattern):
            p = shared if kind == SHARED_ATTN else group_params.get(f"pos{j}")
            if kind == MAMBA:
                h, cache = _mamba_block_with_state(p, h, cfg, knobs)
            else:
                h, cache = _attn_block_with_kv(p, h, positions, cfg, kind,
                                               knobs, max_len)
            caches.append(cache)
        return h, tuple(caches)

    h, caches = jax.lax.scan(group_body, h, params["groups"])
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return logits_fn(params, h[:, -1], cfg), caches
