"""Top-k MoE layer: a dropless layer over the experts one chip holds, and
expert parallelism.

Routing scores every expert (softmax, or sigmoid with a selection bias and
a scale, as in DeepSeek-V3), then ``held_experts`` computes the picks on
the experts held here: sorted by expert into whole row tiles, one grouped
call (the ``moe_experts`` Pallas kernel on TPU) computes every pick, none
dropped. Shared experts are one SwiGLU added to every token.
Distributed mode runs under a FULLY-MANUAL ``shard_map`` (every mesh axis):
tokens are flat-sharded over (pod, data, model), experts are sharded over
``model``, and two ``all_to_all``s move capacity-bounded slots to/from
expert owners.
Under the fsdp_tp policy the expert weights' embed dim is FSDP-sharded over
``data`` and all-gathered on entry (hand-written — partial-manual shard_map
transposes of all_to_all crash XLA CPU, see EXPERIMENTS.md §Dry-run).

Pliant knob: ``top_k`` override (expert perforation) — routing to fewer
experts cuts active FLOPs and all-to-all bytes at bounded quality loss.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, MoEConfig
from repro.models import mlp as mlp_mod
from repro.models.common import ParamSpec
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.dist import annotate


def moe_specs(cfg: ModelConfig):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_expert, m.n_held
    s = {
        # gate stays replicated (tiny): routing must see full d and every
        # expert, held here or not
        "wg": ParamSpec((d, m.n_experts), (None, None)),
        # (experts, F, D): an f-block of one expert is contiguous in HBM
        "wi_gate": ParamSpec((e, f, d), ("expert", "mlp", "embed")),
        "wi_up": ParamSpec((e, f, d), ("expert", "mlp", "embed")),
        "wo": ParamSpec((e, f, d), ("expert", "mlp", "embed")),
    }
    if m.score_bias:
        s["score_bias"] = ParamSpec((m.n_experts,), (None,))
    if m.d_shared:
        s["shared"] = mlp_mod.mlp_specs(cfg, m.d_shared)
    return s


def _scores(x2, params, m: MoEConfig, top_k: int):
    """Routing over every expert, in f32. Returns (ids (T,k), weights
    (T,k) f32, probs (T,E)): experts are picked by score (plus the
    correction bias where the model has one) and weighted by score alone,
    renormalised over the picks and scaled."""
    logits = jnp.dot(x2.astype(jnp.float32),
                     params["wg"].astype(jnp.float32))          # (T, E)
    if m.scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    choice = probs
    if m.score_bias:
        choice = probs + params["score_bias"].astype(jnp.float32)
    _, ids = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(probs, ids, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return ids, w * m.scale, probs


def _aux(probs, ids, n_experts: int):
    """Switch-style load-balance statistic over the routed experts."""
    flat = ids.reshape(-1)
    ce = jnp.bincount(flat, length=n_experts).astype(jnp.float32) / flat.size
    return n_experts * jnp.sum(jnp.mean(probs, axis=0) * ce)


def _capacity(n_tokens: int, top_k: int, n_experts: int, cf: float,
              align: int = 8) -> int:
    c = int(cf * n_tokens * top_k / n_experts)
    return max(align, -(-c // align) * align)


def _route(ids, capacity: int, n_experts: int):
    """Capacity slots of the expert-parallel dispatch. ids: (T,k).
    Returns (slots (T,k), keep (T,k)): picks past an expert's capacity
    are dropped."""
    top_k = ids.shape[1]
    flat_e = ids.reshape(-1)                                # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=n_experts)
    seg_start = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(flat_e.shape[0]) - seg_start[sorted_e]
    keep_sorted = rank < capacity
    slot_sorted = sorted_e * capacity + jnp.minimum(rank, capacity - 1)
    slot = jnp.zeros_like(flat_e).at[order].set(slot_sorted)
    keep = jnp.zeros(flat_e.shape, bool).at[order].set(keep_sorted)
    return slot.reshape(-1, top_k), keep.reshape(-1, top_k)


def _expert_ffn(xe, wi_gate, wi_up, wo, precision: str):
    """xe: (E_loc, C', D); weights (E_loc, F, D)."""
    if precision == "int8":
        def one(x, wg_, wu_, wo_):
            g = jax.nn.silu(kops.quantized_matmul(x, wg_.T).astype(
                jnp.float32))
            u = kops.quantized_matmul(x, wu_.T)
            return kops.quantized_matmul(g.astype(x.dtype) * u, wo_)
        return jax.vmap(one)(xe, wi_gate, wi_up, wo)
    g = jax.nn.silu(jnp.einsum("ecd,efd->ecf", xe, wi_gate,
                               preferred_element_type=jnp.float32))
    u = jnp.einsum("ecd,efd->ecf", xe, wi_up)
    return jnp.einsum("ecf,efd->ecd", g.astype(xe.dtype) * u, wo)


def _moe_ep(params, x2, cfg: ModelConfig, top_k: int, precision: str,
            ep_axis: str):
    """Expert-parallel MoE on local tokens x2: (T, D), inside shard_map
    with the experts sharded over ``ep_axis``: capacity slots move to and
    from the experts' owners by two all_to_alls."""
    m = cfg.moe
    E = m.n_experts
    T = x2.shape[0]
    C = _capacity(T, top_k, E, m.capacity_factor)
    ids, gate, probs = _scores(x2, params, m, top_k)
    slot, keep = _route(ids, C, E)
    flat_slot = slot.reshape(-1)
    flat_keep = keep.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(T), top_k)
    buf = jnp.zeros((E * C, x2.shape[1]), x2.dtype)
    buf = buf.at[flat_slot].add(
        jnp.where(flat_keep[:, None], x2[tok_idx], 0))
    xe = buf.reshape(E, C, -1)
    xe = jax.lax.all_to_all(xe, ep_axis, split_axis=0, concat_axis=1,
                            tiled=True)
    ye = _expert_ffn(xe, params["wi_gate"], params["wi_up"], params["wo"],
                     precision)
    ye = jax.lax.all_to_all(ye, ep_axis, split_axis=1, concat_axis=0,
                            tiled=True)
    y = ye.reshape(E * C, -1)[flat_slot].reshape(T, top_k, -1)
    y = jnp.sum(y * (gate * keep)[..., None].astype(y.dtype), axis=1)
    if m.d_shared:
        y = y + mlp_mod.mlp(params["shared"], x2, precision=precision)
    return y.astype(x2.dtype), _aux(probs, ids, E)


def row_tile(n_tokens: int, top_k: int, n_experts: int) -> int:
    """Rows per tile of the grouped expert call: about twice an expert's
    expected rows, a multiple of 16 (bf16 sublanes), 16 to 128."""
    want = -(-2 * n_tokens * top_k // n_experts)
    return int(min(128, max(16, -(-want // 16) * 16)))


def held_experts(params, x2, cfg: ModelConfig, *, top_k: int = 0,
                 precision: str = "bf16", valid=None, layer=None,
                 use_kernel: Optional[bool] = None, interpret: bool = False):
    """Dropless MoE over the experts held here. x2: (T, D).

    Routes every token over all ``n_experts`` and computes, for each pick
    on a held expert, that expert's SwiGLU of the token: picks are sorted
    by expert and padded per expert to whole row tiles, and one grouped
    call (``kernels.ops.grouped_experts``) runs each tile through its
    expert. No pick is dropped, and picks on experts held elsewhere add
    nothing. The shared experts are added for every token. ``valid`` (T,)
    bool: rows that take no expert rows (admission pads, dead decode
    rows), so a valid row's result never depends on them. ``layer``: the
    expert matrices in ``params`` are stacked over layers and this is the
    layer's index (a layer scan passes the stack whole: the grouped kernel
    reads it in place); None: they are this layer's own.

    Returns (y (T, D), aux, counts (2,) int32): ``counts`` is [expert rows
    computed, held experts with at least one row]."""
    m = cfg.moe
    top_k = top_k or m.top_k
    T, D = x2.shape
    lo, n = m.held_range[0], m.n_held
    ids, w, probs = _scores(x2, params, m, top_k)
    local = ids - lo
    take = (local >= 0) & (local < n)
    if valid is not None:
        take &= valid[:, None]
    grp = jnp.where(take, local, n).reshape(-1)               # n = none
    N = T * top_k
    tm = row_tile(T, top_k, m.n_experts)
    n_tiles = max(1, min(N, (N + n * (tm - 1)) // tm))
    R = n_tiles * tm
    counts = jnp.bincount(grp, length=n + 1)[:n]
    tiles = (counts + tm - 1) // tm
    t_end = jnp.cumsum(tiles)
    t_start = t_end - tiles
    order = jnp.argsort(grp, stable=True)
    g_sorted = grp[order]
    seg = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)])
    first_tile = jnp.concatenate([t_start, jnp.full((1,), n_tiles,
                                                    t_start.dtype)])
    dest_sorted = (first_tile[g_sorted] * tm
                   + jnp.arange(N) - seg[g_sorted])
    dest = jnp.zeros((N,), jnp.int32).at[order].set(
        dest_sorted.astype(jnp.int32))
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)
    row_tok = jnp.zeros((R,), jnp.int32).at[dest].set(tok, mode="drop")
    j = jnp.arange(n_tiles)
    total = t_end[-1]
    jj = jnp.minimum(j, jnp.maximum(total - 1, 0))    # empty: tile before
    tile_e = jnp.minimum(jnp.searchsorted(t_end, jj, side="right"), n - 1)
    tile_n = jnp.where(j < total, jnp.clip(
        counts[tile_e] - (j - t_start[tile_e]) * tm, 0, tm), 0)
    xs = x2[row_tok]
    wg, wu, wo = params["wi_gate"], params["wi_up"], params["wo"]
    scales = None
    if precision == "int8":
        # W8A8 like ``kops.quantized_matmul``: this layer's weights are
        # quantized per output channel on each call, the grouped call runs
        # int8 x int8
        if layer is not None:
            wg, wu, wo, layer = wg[layer], wu[layer], wo[layer], None
        (wg, gs), (wu, us) = (kref.quantize_rowwise(w) for w in (wg, wu))
        wo, os_ = kref.quantize_rowwise(wo, axis=1)
        scales = (jnp.swapaxes(gs, 1, 2)[None], jnp.swapaxes(us, 1, 2)[None],
                  os_[None])
    if layer is None:
        wg, wu, wo, layer = wg[None], wu[None], wo[None], 0
    ys = kops.grouped_experts(xs, wg, wu, wo, tile_e.astype(jnp.int32),
                              tile_n.astype(jnp.int32),
                              jnp.asarray(layer, jnp.int32), scales, tm=tm,
                              use_kernel=use_kernel, interpret=interpret)
    yp = ys[jnp.minimum(dest, R - 1)].reshape(T, top_k, D)
    y = jnp.sum(jnp.where(take[..., None],
                          yp.astype(jnp.float32) * w[..., None], 0.0), axis=1)
    if m.d_shared:
        y = y + mlp_mod.mlp(params["shared"], x2, precision=precision)
    cnt = jnp.stack([jnp.sum(take), jnp.sum(counts > 0)]).astype(jnp.int32)
    return y.astype(x2.dtype), _aux(probs, ids, m.n_experts), cnt


def moe(params, x, cfg: ModelConfig, *, top_k: int = 0,
        precision: str = "bf16", ep_axis: Optional[str] = None,
        mesh=None):
    """x: (B, S, D) -> (y, aux_loss). ``ep_axis``: mesh axis for EP."""
    B, S, D = x.shape
    top_k = top_k or cfg.moe.top_k
    if ep_axis is None or mesh is None:
        y, aux, _ = held_experts(params, x.reshape(-1, D), cfg, top_k=top_k,
                                 precision=precision, use_kernel=False)
        return y.reshape(B, S, D), aux
    assert cfg.moe.n_held == cfg.moe.n_experts, \
        "expert parallelism divides the whole model's experts"

    from jax.sharding import PartitionSpec as P
    T = B * S
    all_axes = tuple(mesh.shape.keys())
    n_all = int(np.prod(list(mesh.shape.values())))
    if T % n_all == 0:
        tok_axes = all_axes                     # flat tokens over every axis
    elif T % mesh.shape[ep_axis] == 0:
        tok_axes = (ep_axis,)                   # decode-size batches
    else:
        y, aux, _ = held_experts(params, x.reshape(-1, D), cfg, top_k=top_k,
                                 precision=precision, use_kernel=False)
        return y.reshape(B, S, D), aux          # tiny batch: replicated
    fsdp = annotate.FSDP_AXIS
    fsdp = fsdp if (fsdp in mesh.shape and
                    cfg.d_model % mesh.shape.get(fsdp, 1) == 0) else None

    def body(params_loc, x_loc):
        p = dict(params_loc)
        if fsdp is not None:                    # hand-written FSDP unshard
            p["wi_gate"] = jax.lax.all_gather(p["wi_gate"], fsdp, axis=2,
                                              tiled=True)
            p["wi_up"] = jax.lax.all_gather(p["wi_up"], fsdp, axis=2,
                                            tiled=True)
            p["wo"] = jax.lax.all_gather(p["wo"], fsdp, axis=2, tiled=True)
        y, aux = _moe_ep(p, x_loc, cfg, top_k, precision, ep_axis)
        for ax in tok_axes:
            aux = jax.lax.pmean(aux, ax)
        return y, aux

    pspec = {
        "wg": P(),
        "wi_gate": P(ep_axis, None, fsdp),
        "wi_up": P(ep_axis, None, fsdp),
        "wo": P(ep_axis, None, fsdp),
    }
    pspec.update({k: jax.tree.map(lambda _: P(), params[k])
                  for k in ("score_bias", "shared") if k in params})
    y2, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P(tok_axes, None)),
        out_specs=(P(tok_axes, None), P()),
        axis_names=set(all_axes), check_vma=False)(params, x.reshape(-1, D))
    return y2.reshape(B, S, D), aux
