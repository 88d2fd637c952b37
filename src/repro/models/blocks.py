"""Per-kind transformer blocks (pre-norm residual) and their param specs."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, LOCAL_ATTN, MAMBA, SHARED_ATTN, ModelConfig
from repro.models.common import ParamSpec, rms_norm
from repro.models import attention as attn_mod
from repro.models import mamba2 as mamba_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.approx.knobs import ApproxKnobs, PRECISE


def block_specs(kind: str, cfg: ModelConfig, *, cross: bool = False,
                dense: bool = False):
    """``dense``: a leading dense layer of an expert model (SwiGLU of
    width ``d_ff`` in place of the experts)."""
    d = cfg.d_model
    if kind == MAMBA:
        return {"norm": ParamSpec((d,), ("embed",), init="ones"),
                "mixer": mamba_mod.mamba_specs(cfg)}
    # attention-family block
    s = {"norm_attn": ParamSpec((d,), ("embed",), init="ones"),
         "attn": (attn_mod.mla_specs(cfg) if cfg.mla
                  else attn_mod.attn_specs(cfg)),
         "norm_mlp": ParamSpec((d,), ("embed",), init="ones")}
    if cross:
        s["norm_cross"] = ParamSpec((d,), ("embed",), init="ones")
        s["cross"] = attn_mod.attn_specs(cfg)
    if cfg.moe is not None and kind in (ATTN, LOCAL_ATTN) and not dense:
        s["moe"] = moe_mod.moe_specs(cfg)
    else:
        s["mlp"] = mlp_mod.mlp_specs(cfg)
    return s


def _serve_ffn(params, hn, cfg: ModelConfig, knobs: ApproxKnobs, *, valid,
               layer, ep_axis, mesh, use_kernel, interpret):
    """The FFN half of a serving block (decode or admission chunk).
    Returns (y, counts (2,) int32): expert rows computed and held experts
    that got one (zero outside the single-device expert layer). ``layer``:
    the expert matrices are stacked over layers, this one's index."""
    prec = knobs.matmul_precision
    counts = jnp.zeros((2,), jnp.int32)
    if "moe" not in params:
        return mlp_mod.mlp(params["mlp"], hn, precision=prec), counts
    if ep_axis is not None and mesh is not None:
        moe_p = params["moe"] if layer is None else dict(
            params["moe"], **{k: params["moe"][k][layer]
                              for k in ("wi_gate", "wi_up", "wo")})
        y, _ = moe_mod.moe(moe_p, hn, cfg, top_k=knobs.topk_override,
                           precision=prec, ep_axis=ep_axis, mesh=mesh)
        return y, counts
    y, _, counts = moe_mod.held_experts(
        params["moe"], hn.reshape(-1, hn.shape[-1]), cfg,
        top_k=knobs.topk_override, precision=prec, valid=valid, layer=layer,
        use_kernel=use_kernel, interpret=interpret)
    return y.reshape(hn.shape), counts


def block_forward(kind: str, params, h, positions, cfg: ModelConfig,
                  knobs: ApproxKnobs = PRECISE, *,
                  ep_axis: Optional[str] = None, mesh=None,
                  enc_out: Optional[jax.Array] = None,
                  causal: bool = True):
    """Full-sequence block. Returns (h, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    prec = knobs.matmul_precision
    if kind == MAMBA:
        h = h + mamba_mod.mamba_mixer(params["mixer"],
                                      rms_norm(h, params["norm"], cfg.norm_eps),
                                      cfg, precision=prec)
        return h, aux
    mode = ("window" if kind == LOCAL_ATTN else
            ("causal" if causal else "full"))
    hn = rms_norm(h, params["norm_attn"], cfg.norm_eps)
    if cfg.mla:
        h = h + attn_mod.mla_attention(params["attn"], hn, positions, cfg,
                                       kv_keep_stride=knobs.kv_keep_stride)
    else:
        h = h + attn_mod.attention(params["attn"], hn, positions, cfg,
                                   mode=mode,
                                   kv_keep_stride=knobs.kv_keep_stride)
    if enc_out is not None:
        h = h + attn_mod.attention(
            params["cross"], rms_norm(h, params["norm_cross"], cfg.norm_eps),
            positions, cfg, mode="cross", kv_x=enc_out)
    hn = rms_norm(h, params["norm_mlp"], cfg.norm_eps)
    if "moe" in params:
        y, aux = moe_mod.moe(params["moe"], hn, cfg,
                             top_k=knobs.topk_override, precision=prec,
                             ep_axis=ep_axis, mesh=mesh)
        h = h + y
    else:
        h = h + mlp_mod.mlp(params["mlp"], hn, precision=prec)
    return h, aux


def block_prefill_paged(kind: str, params, h, positions, cache,
                        cfg: ModelConfig, knobs: ApproxKnobs = PRECISE, *,
                        slot, valid=None, layer=None,
                        ep_axis: Optional[str] = None,
                        mesh=None, dyn_scatter: bool = False,
                        use_kernel: Optional[bool] = None,
                        interpret: bool = False):
    """C-token prompt-chunk step for one slot (serving admission): the
    chunk against the shared page pool / per-slot Mamba rows. h: (1,C,D);
    ``slot`` traced.
    ``valid`` (C,) bool marks the rows whose K/V reach the slot's pages
    (``attention.paged_chunk_attention``) and that take expert rows; a
    Mamba layer takes every row. ``layer``: paged pools and expert
    matrices come stacked over layers (``lm.scan_layers``), this one's
    index. Returns (h, new_cache, counts) — counts as ``_serve_ffn``.
    """
    counts = jnp.zeros((2,), jnp.int32)
    prec = knobs.matmul_precision
    if kind == MAMBA:
        # slice the slot's state row out, run the chunk, scatter it back —
        # a masked select, keeping every leaf's batch dim intact for GSPMD
        row = jax.tree.map(lambda x: jnp.take(x, slot[None], axis=0), cache)
        y, row2 = mamba_mod.mamba_prefill(
            params["mixer"], rms_norm(h, params["norm"], cfg.norm_eps),
            row, cfg, precision=prec)
        B = cache.state.shape[0]
        smask = jnp.arange(B) == slot
        new_cache = jax.tree.map(
            lambda old, new: jnp.where(
                smask.reshape((B,) + (1,) * (old.ndim - 1)), new, old),
            cache, row2)
        return h + y, new_cache, counts
    window = cfg.window if kind == LOCAL_ATTN else 0
    kv_scale = attn_mod.KV_SCALE if knobs.kv_quant else 0.0
    hn = rms_norm(h, params["norm_attn"], cfg.norm_eps)
    if isinstance(cache, attn_mod.PagedLatentCache):
        y, new_cache = attn_mod.paged_latent_chunk_attention(
            params["attn"], hn, positions, cache, cfg, slot, layer=layer,
            kv_scale=kv_scale, valid=valid, mesh=mesh)
    else:
        y, new_cache = attn_mod.paged_chunk_attention(
            params["attn"], hn, positions, cache, cfg, slot, layer=layer,
            window=window, kv_scale=kv_scale, valid=valid,
            dyn_scatter=dyn_scatter, mesh=mesh, use_kernel=use_kernel,
            interpret=interpret)
    h = h + y
    y, counts = _serve_ffn(params, rms_norm(h, params["norm_mlp"],
                                            cfg.norm_eps), cfg, knobs,
                           valid=valid, layer=layer, ep_axis=ep_axis,
                           mesh=mesh, use_kernel=use_kernel,
                           interpret=interpret)
    return h + y, new_cache, counts


def block_decode(kind: str, params, h, position, cache, cfg: ModelConfig,
                 knobs: ApproxKnobs = PRECISE, *,
                 ep_axis: Optional[str] = None, mesh=None,
                 enc_out: Optional[jax.Array] = None, active=None,
                 use_kernel: Optional[bool] = None,
                 dyn_scatter: bool = False, interpret: bool = False,
                 layer=None):
    """Single-token decode. Returns (h, new_cache, counts); ``layer`` as
    ``block_prefill_paged``.

    ``active`` (B,) bool masks per-slot cache writes (paged engines whose
    decode interleaves with background admission); None = all rows live.
    This is also the megastep scan body's per-row FREEZE contract
    (``lm.decode_megastep``): for a row with ``active=False``, every cache
    leaf the row owns must come back bit-identical — the paged write paths
    guarantee it by redirecting the row's scatter to the never-read null
    page (dyn_scatter / sharded kernel) or masking it out of the one-hot
    select, and ``mamba_decode`` by where-masking the state update. A
    row that dies mid-megastep (EOS / budget) therefore stops mutating
    its pages and SSM rows immediately, without a host round-trip.
    ``use_kernel`` forwards the paged-attention dispatch override;
    ``dyn_scatter`` selects the dynamic-index cache write for unsharded
    paged pools; under a ``mesh`` the paged path shard_maps the fused
    kernel when the pool layout allows (``attention.paged_decode_attention``)
    and ``interpret`` runs that kernel in Pallas interpret mode (CPU CI)."""
    counts = jnp.zeros((2,), jnp.int32)
    prec = knobs.matmul_precision
    if kind == MAMBA:
        y, new_cache = mamba_mod.mamba_decode(
            params["mixer"], rms_norm(h, params["norm"], cfg.norm_eps),
            cache, cfg, precision=prec, active=active)
        return h + y, new_cache, counts
    window = cfg.window if kind == LOCAL_ATTN else 0
    kv_scale = attn_mod.KV_SCALE if knobs.kv_quant else 0.0
    hn = rms_norm(h, params["norm_attn"], cfg.norm_eps)
    if isinstance(cache, attn_mod.PagedLatentCache):
        y, new_cache = attn_mod.paged_latent_decode_attention(
            params["attn"], hn, position, cache, cfg, layer=layer,
            kv_scale=kv_scale, active=active, use_kernel=use_kernel,
            mesh=mesh, interpret=interpret)
    elif isinstance(cache, attn_mod.PagedKVCache):
        y, new_cache = attn_mod.paged_decode_attention(
            params["attn"], hn, position, cache, cfg, layer=layer,
            window=window, kv_scale=kv_scale, active=active,
            use_kernel=use_kernel, dyn_scatter=dyn_scatter, mesh=mesh,
            interpret=interpret)
    else:
        y, new_cache = attn_mod.decode_attention(
            params["attn"], hn, position, cache, cfg, window=window,
            kv_scale=kv_scale)
    h = h + y
    if enc_out is not None:
        h = h + attn_mod.attention(
            params["cross"], rms_norm(h, params["norm_cross"], cfg.norm_eps),
            position[:, None], cfg, mode="cross", kv_x=enc_out)
    y, counts = _serve_ffn(params, rms_norm(h, params["norm_mlp"],
                                            cfg.norm_eps), cfg, knobs,
                           valid=active, layer=layer, ep_axis=ep_axis,
                           mesh=mesh, use_kernel=use_kernel,
                           interpret=interpret)
    return h + y, new_cache, counts
