"""Logical-axis -> mesh-axis sharding policies.

Every module in ``models/`` declares its parameters as ``ParamSpec`` trees
with *logical* axis names (``embed``, ``mlp``, ``q_heads``, ``expert``, ...).
This module maps those names onto mesh axes under a named policy and returns
``NamedSharding`` trees with the exact same pytree structure as the params —
so ``jax.device_put(params, param_shardings(...))`` and
``jax.jit(..., in_shardings=...)`` work directly.

Policies:

* ``"replicated"`` — everything everywhere (CPU smoke fallback).
* ``"tp"``         — megatron-style tensor parallelism over ``model``:
                     hidden/expert/vocab dims sharded, embed dim replicated.
* ``"fsdp_tp"``    — ``tp`` plus the embed dim FSDP-sharded over ``data``.

A dim is only sharded when its size divides the mesh axis; each mesh axis is
used at most once per array (first matching dim wins), so e.g. MoE expert
weights shard experts over ``model`` and leave ``mlp`` replicated rather than
double-booking the axis.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.common import ParamSpec, spec_tree_map

# logical axis name -> mesh axis, per policy. Axes not listed stay replicated.
_TP_RULES = {
    "vocab": "model",
    "q_heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "expert": "model",
    "ssm_inner": "model",
    "ssm_heads": "model",
}

POLICIES = {
    "replicated": {},
    "tp": dict(_TP_RULES),
    "fsdp_tp": dict(_TP_RULES, embed="data"),
}


def default_policy(cfg: ModelConfig) -> str:
    """Weights at production scale never fit replicated: FSDP+TP everywhere."""
    return "fsdp_tp"


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    flat = axes if isinstance(axes, tuple) else (axes,)
    n = 1
    for a in flat:
        n *= mesh.shape[a]
    return n


def _axes_present(mesh, axes) -> bool:
    flat = axes if isinstance(axes, tuple) else (axes,)
    return all(a in mesh.shape for a in flat)


def _spec_for(spec: ParamSpec, rules, mesh) -> P:
    used = set()
    out = []
    for size, name in zip(spec.shape, spec.axes):
        ax = rules.get(name)
        if (ax is None or ax not in mesh.shape or ax in used
                or size % mesh.shape[ax] != 0):
            out.append(None)
        else:
            out.append(ax)
            used.add(ax)
    return P(*out)


def param_shardings(cfg: ModelConfig, mesh, mode: Optional[str] = None):
    """NamedSharding tree matching the param tree of ``cfg``'s family."""
    from repro.models import api
    rules = POLICIES[mode or default_policy(cfg)]
    return spec_tree_map(
        lambda s: NamedSharding(mesh, _spec_for(s, rules, mesh)),
        api.model_specs(cfg))


# ----------------------------------------------------------------- inputs --

def batch_pspec(global_batch: int, mesh) -> P:
    """PartitionSpec for the batch dim: greedily shard over (pod, data)."""
    use, n = [], 1
    for a in ("pod", "data"):
        if a in mesh.shape and global_batch % (n * mesh.shape[a]) == 0:
            use.append(a)
            n *= mesh.shape[a]
    if not use:
        return P()
    return P(tuple(use) if len(use) > 1 else use[0])


def input_shardings(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Shardings matching ``api.input_specs(cfg, shape)`` key-for-key."""
    from repro.models import api
    bspec = batch_pspec(shape.global_batch, mesh)
    b = bspec[0] if len(bspec) else None
    return {
        name: NamedSharding(mesh, P(b, *([None] * (len(s.shape) - 1))))
        for name, s in api.input_specs(cfg, shape).items()
    }


# ------------------------------------------------------- paged decode plan --

class PagedDecodePlan:
    """Slot-affinity layout of the sharded fused paged decode: the batch
    mesh axes the slot/page dims split over, the resulting shard count, and
    the mesh axis (if any) the kv_heads dim additionally splits over.

    The plan is a pure function of (cfg, mesh, batch_slots, n_pages), so the
    engine (pool sizing), ``cache_shardings`` (device placement), and the
    traced decode step (shard_map specs + block-table rebasing) all derive
    the SAME layout independently — no side channel between host allocator
    and compiled executable."""

    def __init__(self, batch_axes, n_shards: int, kv_head_axis):
        self.batch_axes = batch_axes      # mesh axis name or tuple of names
        self.n_shards = n_shards
        self.kv_head_axis = kv_head_axis  # "model" or None (replicated)

    def __repr__(self):
        return (f"PagedDecodePlan(batch_axes={self.batch_axes!r}, "
                f"n_shards={self.n_shards}, "
                f"kv_head_axis={self.kv_head_axis!r})")


def paged_decode_plan(cfg: ModelConfig, mesh, batch_slots: int,
                      n_pages: int = 0):
    """(plan, reason) for sharding the fused paged-attention decode kernel.

    Returns ``(PagedDecodePlan, "")`` when the pool can be split with slot
    affinity — slots and physical pages partitioned over the same batch
    axes, so each device's kernel invocation resolves its block tables
    entirely against local pages — else ``(None, reason)`` and the caller
    falls back to the GSPMD gather path. ``n_pages`` <= 0 skips the page-dim
    divisibility check (pool sizing rounds it up to fit afterwards)."""
    if mesh is None:
        return None, "no mesh (single device)"
    bspec = batch_pspec(batch_slots, mesh)
    if not len(bspec):
        return None, (f"batch_slots={batch_slots} does not divide any batch "
                      "mesh axis — slots cannot split with affinity")
    b = bspec[0]
    n = _axis_size(mesh, b)
    if n_pages > 0 and n_pages % n != 0:
        return None, (f"n_pages={n_pages} does not split over batch axes "
                      f"{b!r} (size {n})")
    g_ax = ("model" if ("model" in mesh.shape
                        and cfg.n_kv_heads % mesh.shape["model"] == 0)
            else None)
    return PagedDecodePlan(b, n, g_ax), ""


# ------------------------------------------------------- ring prefill plan --

class PrefillPlan:
    """Sequence layout of the ring-attention chunked-prefill cell: the single
    mesh axis the chunk's query dim (and the rotating K/V context) splits
    over, the resulting shard count, and the mesh axis (if any) the kv_heads
    dim additionally splits over.

    Like ``PagedDecodePlan``, the plan is a pure function of
    ``(cfg, mesh, chunk_len)`` so the prefill cell (shard_map specs + ring
    schedule), the admission-step builders, and the explorer's compile-time
    pricing all derive the SAME sequence layout independently — no side
    channel between them. Causal chunks are laid out *striped* (round-robin
    query rows per shard) for ring load balance; window chunks stay
    contiguous so whole hops outside the band can be skipped — that choice
    is per attention call, not part of the plan."""

    def __init__(self, seq_axis: str, n_shards: int, kv_head_axis):
        self.seq_axis = seq_axis          # single mesh axis name
        self.n_shards = n_shards
        self.kv_head_axis = kv_head_axis  # "model" or None (replicated)

    def __repr__(self):
        return (f"PrefillPlan(seq_axis={self.seq_axis!r}, "
                f"n_shards={self.n_shards}, "
                f"kv_head_axis={self.kv_head_axis!r})")


def prefill_plan(cfg: ModelConfig, mesh, chunk_len: int):
    """(plan, reason) for sequence-sharding one admission chunk's attention.

    Returns ``(PrefillPlan, "")`` when a batch-side mesh axis can carry the
    ring — a single axis from ("pod", "data") with size > 1 that does not
    exceed the chunk length (each shard needs at least one resident query
    row) — else ``(None, reason)`` and the caller takes the loud GSPMD
    unsharded path. A single axis keeps the ``ppermute`` ring schedule
    trivial; the largest eligible axis wins. kv_heads additionally split
    over ``model`` when divisible, mirroring the decode plan."""
    if mesh is None:
        return None, "no mesh (single device)"
    cand = [a for a in ("pod", "data") if mesh.shape.get(a, 1) > 1]
    if not cand:
        return None, ("no batch mesh axis (pod/data) with size > 1 to carry "
                      "the sequence ring")
    cand = [a for a in cand if mesh.shape[a] <= chunk_len]
    if not cand:
        return None, (f"chunk_len={chunk_len} shorter than every batch mesh "
                      "axis — no resident query row per shard")
    ax = max(cand, key=lambda a: mesh.shape[a])
    g_ax = ("model" if ("model" in mesh.shape
                        and cfg.n_kv_heads % mesh.shape["model"] == 0)
            else None)
    return PrefillPlan(ax, mesh.shape[ax], g_ax), ""


# ----------------------------------------------------------------- caches --

def cache_shardings(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                    seq_axis: str = "model", quantized: bool = False,
                    paged=None):
    """(sharding tree, abstract caches) for sequence-sharded decode.

    Dense KV caches shard the cache-length dim over ``seq_axis`` (GSPMD
    lowers the attention softmax over it to partial reductions) and the
    batch dim over the batch axes. Paged pools (``paged`` = PageSpec) come
    in two layouts: a slot-affinity spec (``n_shards`` > 1) shards the
    physical-page dim over the BATCH axes — the same contiguous split the
    block table's slot dim gets, so a slot's pages are device-local and the
    fused kernel runs per-shard under shard_map — with the kv_heads dim
    optionally split over ``model``; a legacy spec shards the page dim over
    ``seq_axis`` (the gather and the one-hot scatter are both elementwise
    over it). Mamba states have no sequence dim; they shard batch only.
    Returns trees with the exact structure of ``init_caches`` /
    ``init_paged_caches``.
    """
    from repro.models import api
    from repro.models.attention import KVCache, PagedKVCache
    from repro.models.mamba2 import MambaCache
    caches_abs = api.abstract_caches(cfg, shape.global_batch, shape.seq_len,
                                     quantized=quantized, paged=paged)
    bspec = batch_pspec(shape.global_batch, mesh)
    b = bspec[0] if len(bspec) else None

    def batch_ax(n):
        return b if (b is not None and n % _axis_size(mesh, b) == 0) else None

    def seq_ax(n):
        ok = (seq_axis in mesh.shape and n % mesh.shape[seq_axis] == 0)
        return seq_axis if ok else None

    def one(c):
        # leaves are group-stacked: dim 0 = layer groups (scan carried)
        if isinstance(c, PagedKVCache):
            if getattr(paged, "n_shards", 1) > 1:
                # slot-affinity layout: pages split over the batch axes like
                # the slots themselves; kv_heads over model when divisible
                pg = batch_ax(c.kp.shape[1])
                g_ax = ("model" if ("model" in mesh.shape and
                                    c.kp.shape[3] % mesh.shape["model"] == 0)
                        else None)
                kv = NamedSharding(mesh, P(None, pg, None, g_ax, None))
                return PagedKVCache(
                    kp=kv, vp=kv,
                    ppos=NamedSharding(mesh, P(None, pg, None)),
                    block=NamedSharding(
                        mesh, P(None, batch_ax(c.block.shape[1]), None)))
            pg = seq_ax(c.kp.shape[1])
            kv = NamedSharding(mesh, P(None, pg, None, None, None))
            return PagedKVCache(
                kp=kv, vp=kv,
                ppos=NamedSharding(mesh, P(None, pg, None)),
                block=NamedSharding(mesh,
                                    P(None, batch_ax(c.block.shape[1]), None)))
        if isinstance(c, KVCache):
            bb, ss = batch_ax(c.k.shape[1]), seq_ax(c.k.shape[2])
            kv = NamedSharding(mesh, P(None, bb, ss, None, None))
            return KVCache(
                k=kv, v=kv,
                pos=NamedSharding(mesh, P(None, bb, ss)),
                cursor=NamedSharding(mesh, P(None)))
        assert isinstance(c, MambaCache), type(c)
        return jax.tree.map(
            lambda x: NamedSharding(
                mesh, P(None, batch_ax(x.shape[1]), *([None] * (x.ndim - 2)))),
            c)

    sh = jax.tree.map(one, caches_abs,
                      is_leaf=lambda x: isinstance(
                          x, (KVCache, PagedKVCache, MambaCache)))
    return sh, caches_abs


def megastep_shardings(param_sh, cache_sh):
    """jit sharding specs for the fused K-token megastep executable.

    Signature (``train.step.make_paged_megastep``): ``step(params, cur,
    pos, alive, uids, draws, budget, caches) -> (toks, cur, pos, alive,
    draws, budget, counts, caches)``. Params and caches keep the engine's
    derived layouts — the caches spec appearing in BOTH positions is what
    lets the engine donate argument 7 and have XLA alias the pool in place
    across the whole K-step scan. The (B,)-shaped per-row carries (and the
    (B, K) token output and the expert counters) ride replicated: a few hundred bytes, not worth a
    collective, and the host reads them whole at the drain point.
    """
    in_sh = (param_sh, None, None, None, None, None, None, cache_sh)
    out_sh = (None, None, None, None, None, None, None, cache_sh)
    return in_sh, out_sh
