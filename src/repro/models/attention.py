"""GQA attention: specs, train/prefill forward (chunked, static-sliced causal),
banded sliding-window path, cross-attention, and cached decode.

The full-sequence causal path unrolls over query chunks with *static* growing
KV slices, so compiled HLO FLOPs match true causal cost (no masked-waste) —
this is the reference path the dry-run compiles.

Approximation hook (Pliant "loop perforation" applied to attention): a static
``kv_keep_stride`` > 1 drops off-diagonal KV chunks with stride, cutting both
FLOPs and HBM traffic of the attention loop at bounded quality loss.
"""
from __future__ import annotations

import collections
import sys
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec, apply_rope, rms_norm, softcap


def attn_specs(cfg: ModelConfig):
    d, q, kv = cfg.d_model, cfg.q_dim, cfg.kv_dim
    return {
        "wq": ParamSpec((d, q), ("embed", "q_heads")),
        "wk": ParamSpec((d, kv), ("embed", "kv_heads")),
        "wv": ParamSpec((d, kv), ("embed", "kv_heads")),
        "wo": ParamSpec((q, d), ("q_heads", "embed")),
    }


def _split_heads(x, n_heads, head_dim):
    return x.reshape(x.shape[:-1] + (n_heads, head_dim))


def _sdpa(q, k, v, *, mask=None, cap: float = 0.0):
    """q: (B,Sq,G,R,hd) k/v: (B,Skv,G,hd). Softmax in fp32."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bsgrh,btgh->bgrst", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, cap) if cap else s
    s = s.astype(jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrst,btgh->bsgrh", p, v)


def _merge(o, B, Sq, q_dim):
    return o.reshape(B, Sq, q_dim)


def default_q_chunk(seq_len: int) -> int:
    """Bound the per-chunk fp32 score tile (chunk x S) at long sequences:
    32k sequences with 1024-wide chunks cost 6+ GiB of transient scores per
    layer (EXPERIMENTS.md §Perf); 256-wide chunks cap it at ~1.6 GiB."""
    if seq_len <= 8192:
        return 1024
    return 256


def attention(params, x, positions, cfg: ModelConfig, *,
              mode: str = "causal",          # causal | window | cross | full
              kv_x: Optional[jax.Array] = None,
              q_chunk: int = 0,
              kv_keep_stride: int = 1,
              rope: bool = True):
    """Full-sequence attention. x: (B,S,D). Returns (B,S,D)."""
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    G, R = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    src = x if kv_x is None else kv_x
    q = _split_heads(x @ params["wq"], cfg.n_heads, hd)
    k = _split_heads(src @ params["wk"], cfg.n_kv_heads, hd)
    v = _split_heads(src @ params["wv"], cfg.n_kv_heads, hd)
    if rope and mode != "cross":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = q.reshape(B, S, G, R, hd)

    q_chunk = q_chunk or default_q_chunk(S)
    if mode == "window":
        o = _banded(q, k, v, cfg.window, cap=cfg.attn_softcap)
    elif mode in ("cross", "full"):
        o = _sdpa(q, k, v, cap=cfg.attn_softcap)
    else:
        o = _causal_chunked(q, k, v, q_chunk=q_chunk,
                            kv_keep_stride=kv_keep_stride,
                            cap=cfg.attn_softcap)
    return _merge(o, B, S, cfg.q_dim) @ params["wo"]


def _causal_chunked(q, k, v, *, q_chunk: int, kv_keep_stride: int, cap: float):
    """Unrolled q-chunk loop; chunk i sees kv[: (i+1)*C] via static slices.

    With ``kv_keep_stride=p``: off-diagonal KV chunks are perforated — chunk i
    keeps its diagonal + previous chunk, and every p-th older chunk.
    """
    B, S, G, R, hd = q.shape
    C = min(q_chunk, S)
    assert S % C == 0, (S, C)
    n = S // C
    # positions within the full sequence for masking the diagonal chunk
    outs = []
    for i in range(n):
        qi = q[:, i * C:(i + 1) * C]
        if kv_keep_stride <= 1 or i <= 1:
            ki, vi = k[:, : (i + 1) * C], v[:, : (i + 1) * C]
            kv_pos = jnp.arange((i + 1) * C)
        else:
            # keep chunks: every `stride`-th old chunk + chunk i-1 + diagonal i
            keep = [j for j in range(i - 1) if j % kv_keep_stride == 0] + [i - 1, i]
            ki = jnp.concatenate([k[:, j * C:(j + 1) * C] for j in keep], axis=1)
            vi = jnp.concatenate([v[:, j * C:(j + 1) * C] for j in keep], axis=1)
            kv_pos = jnp.concatenate(
                [jnp.arange(j * C, (j + 1) * C) for j in keep])
        q_pos = jnp.arange(i * C, (i + 1) * C)
        mask = kv_pos[None, :] <= q_pos[:, None]           # (C, Skv_i)
        outs.append(_sdpa(qi, ki, vi,
                          mask=mask[None, None, None], cap=cap))
    return jnp.concatenate(outs, axis=1)


def _banded(q, k, v, window: int, *, cap: float):
    """Sliding-window causal attention as block-band: each W-block of queries
    attends to its own + previous KV block, masked to the exact window."""
    B, S, G, R, hd = q.shape
    W = min(window, S)
    assert S % W == 0, (S, W)
    n = S // W
    qb = q.reshape(B, n, W, G, R, hd)
    kb = k.reshape(B, n, W, G, hd)
    vb = v.reshape(B, n, W, G, hd)
    # previous block (block -1 = zeros, fully masked)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    k2 = jnp.concatenate([kprev, kb], axis=2)              # (B,n,2W,G,hd)
    v2 = jnp.concatenate([vprev, vb], axis=2)
    q_pos = jnp.arange(W)[:, None]                         # in-block
    kv_pos = jnp.arange(2 * W)[None, :] - W                # relative to block
    mask = (kv_pos <= q_pos) & (kv_pos > q_pos - W)
    first = jnp.arange(n)[:, None, None] > 0               # block0 has no prev
    mask = mask[None] & (first | (kv_pos[None] >= 0))
    scale = hd ** -0.5
    s = jnp.einsum("bnsgrh,bntgh->bngrst", qb, k2,
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, cap) if cap else s
    s = jnp.where(mask[None, :, None, None, :, :],
                  s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bngrst,bntgh->bnsgrh", p, v2)
    return o.reshape(B, S, G, R, hd)


# ------------------------------------------------------------------ decode --

# Global static scale of the int8-quantized serving KV cache (the ``kv_quant``
# knob). Shared by decode, chunked prefill, and the engine's cache-dtype
# conversion on a variant hot-swap — all three must round identically, so
# they all go through the two helpers below.
KV_SCALE = 0.05


def quantize_kv(x, scale: float = KV_SCALE):
    return jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                    -127, 127).astype(jnp.int8)


def dequantize_kv(x, dtype, scale: float = KV_SCALE):
    return x.astype(dtype) * scale


class KVCache(NamedTuple):
    k: jax.Array          # (B, W_cache, G, hd)
    v: jax.Array
    pos: jax.Array        # (B, W_cache) absolute positions, -1 = empty
    cursor: jax.Array     # scalar int32: next write slot (ring)


def init_cache(cfg: ModelConfig, batch: int, length: int, dtype=jnp.bfloat16,
               quantized: bool = False) -> KVCache:
    hd = cfg.resolved_head_dim
    kdt = jnp.int8 if quantized else dtype
    shape = (batch, length, cfg.n_kv_heads, hd)
    return KVCache(
        k=jnp.zeros(shape, kdt), v=jnp.zeros(shape, kdt),
        pos=jnp.full((batch, length), -1, jnp.int32),
        cursor=jnp.zeros((), jnp.int32))


def decode_attention(params, x, position, cache: KVCache, cfg: ModelConfig, *,
                     window: int = 0, kv_scale: float = 0.0, rope: bool = True):
    """One-token decode. x: (B,1,D); position: (B,) absolute position.

    Returns (out (B,1,D), new_cache). Ring-buffer cache: local layers size W,
    global layers size max_seq. ``kv_scale``>0 → int8-quantized cache entries.
    """
    B, one, D = x.shape
    hd = cfg.resolved_head_dim
    G, R = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    q = _split_heads(x @ params["wq"], cfg.n_heads, hd)
    k = _split_heads(x @ params["wk"], G, hd)
    v = _split_heads(x @ params["wv"], G, hd)
    if rope:
        q = apply_rope(q, position[:, None], cfg.rope_theta)
        k = apply_rope(k, position[:, None], cfg.rope_theta)
    W = cache.k.shape[1]
    slot = cache.cursor % W
    if kv_scale:
        k_store = quantize_kv(k, kv_scale)
        v_store = quantize_kv(v, kv_scale)
    else:
        k_store, v_store = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
    # one-hot masked write, NOT dynamic_update_slice: a DUS at a traced index
    # across the sequence-SHARDED cache dim makes GSPMD all-gather the whole
    # cache every step (observed 40x decode memory traffic + 0.4s collectives
    # on mistral decode_32k — EXPERIMENTS.md §Perf); the masked select is
    # elementwise over the sharded dim and partitions cleanly.
    wmask = (jnp.arange(W) == slot)
    nk = jnp.where(wmask[None, :, None, None], k_store, cache.k)
    nv = jnp.where(wmask[None, :, None, None], v_store, cache.v)
    npos = jnp.where(wmask[None, :], position[:, None], cache.pos)
    new_cache = KVCache(nk, nv, npos, cache.cursor + 1)

    kk = dequantize_kv(nk, q.dtype, kv_scale) if kv_scale else \
        nk.astype(q.dtype)
    vv = dequantize_kv(nv, q.dtype, kv_scale) if kv_scale else \
        nv.astype(q.dtype)
    qg = q.reshape(B, 1, G, R, hd)
    valid = npos >= 0
    if window:
        valid &= npos > (position[:, None] - window)
    valid &= npos <= position[:, None]
    o = _sdpa(qg, kk, vv, mask=valid[:, None, None, None, :],
              cap=cfg.attn_softcap)
    return _merge(o, B, 1, cfg.q_dim) @ params["wo"], new_cache


# ------------------------------------------------------------------- paged --

class PagedKVCache(NamedTuple):
    """Paged decode cache: entries live in a shared physical page pool and
    each batch slot maps logical pages (position // page_size) to physical
    pages through its block-table row. Physical page 0 is the reserved
    null/trash page: unmapped block entries point at it and are masked out
    of attention, and inactive decode rows scatter into it harmlessly.
    Allocation is host-side (``serve.pages.PagePool``); the jitted paths
    below only gather/scatter through the tables. They take the cache
    stacked over layers (leading axis) with the layer's index, as the
    latent functions take ``PagedLatentCache``: the layer scan carries the
    whole stack, so entries are written in place and the kernel reads its
    layer without a copy."""
    kp: jax.Array         # (n_pages, page_size, G, hd) physical page pool
    vp: jax.Array
    ppos: jax.Array       # (n_pages, page_size) absolute positions, -1 empty
    block: jax.Array      # (B, max_pages) int32 physical page ids, 0 = unmapped


def init_paged_cache(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int, max_pages: int, dtype=jnp.bfloat16,
                     quantized: bool = False) -> PagedKVCache:
    hd = cfg.resolved_head_dim
    kdt = jnp.int8 if quantized else dtype
    shape = (n_pages, page_size, cfg.n_kv_heads, hd)
    return PagedKVCache(
        kp=jnp.zeros(shape, kdt), vp=jnp.zeros(shape, kdt),
        ppos=jnp.full((n_pages, page_size), -1, jnp.int32),
        block=jnp.zeros((batch, max_pages), jnp.int32))


def _page_scatter(sel, write, buf, layer, new):
    """Scatter ``new`` rows into layer ``layer`` of the stacked page pool
    through a one-hot selection — NOT a dynamic-index scatter: indices stay
    on the unsharded (page, offset) dims as an elementwise one-hot, so a
    pool sharded over pages or heads partitions cleanly (same GSPMD hazard
    class as the dense ring write); only the unsharded layer dim is indexed.

    sel: (R, n_pages, P) one-hot; write: (n_pages, P) = sel.any(0);
    buf: (L, n_pages, P, ...); new: (R, ...). Colliding rows (inactive
    decode slots all aimed at the trash page) sum to garbage that is never
    read.
    """
    scat = jnp.einsum("rnp,r...->np...", sel.astype(jnp.float32),
                      new.astype(jnp.float32))
    expand = (None,) * (buf.ndim - 3)
    return buf.at[layer].set(
        jnp.where(write[(slice(None), slice(None)) + expand],
                  scat.astype(buf.dtype), buf[layer]))


def _gather_pages(cache: PagedKVCache, layer, block, q_positions, *,
                  window: int):
    """Gather a block table's pages of stacked layer ``layer`` into
    contiguous K/V + validity mask.

    block: (B, M); q_positions: (B, C) absolute query positions. Returns
    (k (B, M*P, G, hd), v, valid (B, C, M*P)). Unmapped entries (physical
    page 0) are masked regardless of the trash page's contents.
    """
    P = cache.ppos.shape[-1]
    B, M = block.shape
    gk = cache.kp[layer, block].reshape(B, M * P, *cache.kp.shape[3:])
    gv = cache.vp[layer, block].reshape(B, M * P, *cache.vp.shape[3:])
    gpos = cache.ppos[layer, block].reshape(B, M * P)
    mapped = jnp.repeat(block != 0, P, axis=1)            # (B, M*P)
    valid = mapped[:, None, :] & (gpos[:, None, :] >= 0)
    valid &= gpos[:, None, :] <= q_positions[:, :, None]
    if window:
        valid &= gpos[:, None, :] > q_positions[:, :, None] - window
    return gk, gv, gpos, valid


# Trace-time audit of which paged-decode path each compile took, keyed by
# dispatch outcome (kernel_sharded / gather_mesh / kernel_single /
# gather_single). Counts bump while TRACING, so after a jitted step is
# compiled the counter tells tests which path is in the executable — the
# gather fallback under a mesh is otherwise invisible from outside.
DISPATCH_COUNTS: "collections.Counter[str]" = collections.Counter()

_GATHER_WARNED = set()


def _warn_gather(reason: str) -> None:
    """One line per distinct reason: a mesh silently paying O(slots x
    max_len) gather traffic was the regression class this replaces."""
    if reason in _GATHER_WARNED:
        return
    _GATHER_WARNED.add(reason)
    print("repro: paged decode under a mesh is taking the GSPMD dense "
          f"gather path — {reason}; the fused kernel is not sharded, so "
          "decode HBM traffic is O(slots x max_len) per device",
          file=sys.stderr)


def explain_dispatch(cfg: ModelConfig, mesh, *, batch_slots: int,
                     n_pages: int = 0,
                     use_kernel: Optional[bool] = None,
                     megastep_k: int = 0) -> str:
    """One-line description of the paged-decode path this configuration
    dispatches to (surfaced by ``launch/serve.py`` at startup).
    ``megastep_k > 0`` notes that the decode cell runs inside a fused
    K-step scan (one executable dispatch per K tokens) — the attention
    dispatch decision itself is identical per scan iteration."""
    from repro.kernels import ops as kops
    if use_kernel is None:
        use_kernel = kops._on_tpu()
    mega = (f", inside a fused {megastep_k}-token megastep scan"
            if megastep_k > 0 else "")
    if mesh is None:
        return (f"paged decode: fused Pallas kernel, single device{mega}"
                if use_kernel else
                "paged decode: dense gather reference, single device "
                f"(kernel off: not on TPU){mega}")
    if not use_kernel:
        return ("paged decode: GSPMD dense gather under mesh "
                f"(kernel off: not on TPU){mega}")
    from repro.dist.sharding import paged_decode_plan
    plan, reason = paged_decode_plan(cfg, mesh, batch_slots, n_pages)
    if plan is not None:
        heads = (f"kv_heads over {plan.kv_head_axis!r}"
                 if plan.kv_head_axis else "kv_heads replicated")
        return ("paged decode: fused kernel shard_map'd over "
                f"{plan.batch_axes!r} ({plan.n_shards} slot-affinity "
                f"shards, {heads}){mega}")
    return ("paged decode: GSPMD dense gather FALLBACK under mesh — "
            f"{reason}{mega}")


def _warn_prefill(reason: str) -> None:
    """Prefill's mirror of ``_warn_gather``: a mesh silently running every
    admission chunk's attention whole on each device is the idle-7-of-8
    regression class the ring replaces."""
    key = "prefill:" + reason
    if key in _GATHER_WARNED:
        return
    _GATHER_WARNED.add(key)
    print("repro: chunked-prefill admission under a mesh is taking the "
          f"GSPMD unsharded path — {reason}; each chunk's attention runs "
          "whole per device (no sequence parallelism)", file=sys.stderr)


def _prefill_ring_plan(cfg: ModelConfig, mesh, chunk_len: int,
                       use_kernel: Optional[bool]):
    """The (plan, reason) both chunk cells dispatch on, with the trace-time
    counter bump (ring_prefill / prefill_gather_mesh / prefill_single) and
    the loud fallback warning — prefill's mirror of the paged-decode
    dispatch block."""
    from repro.kernels import ops as kops
    if mesh is None:
        DISPATCH_COUNTS["prefill_single"] += 1
        return None, "no mesh (single device)"
    if use_kernel is None:
        use_kernel = kops._on_tpu()
    if not use_kernel:
        plan, reason = None, "kernel off: not on TPU"
    else:
        from repro.dist.sharding import prefill_plan
        plan, reason = prefill_plan(cfg, mesh, chunk_len)
    if plan is not None:
        DISPATCH_COUNTS["ring_prefill"] += 1
        return plan, ""
    DISPATCH_COUNTS["prefill_gather_mesh"] += 1
    _warn_prefill(reason)
    return None, reason


def explain_prefill_dispatch(cfg: ModelConfig, mesh, *, chunk_len: int,
                             use_kernel: Optional[bool] = None) -> str:
    """One-line description of the chunked-prefill admission path this
    configuration dispatches to (surfaced next to ``explain_dispatch`` in
    the ``launch/serve.py`` startup banner)."""
    from repro.kernels import ops as kops
    if use_kernel is None:
        use_kernel = kops._on_tpu()
    if mesh is None:
        return "chunked prefill: whole-chunk admission cell, single device"
    if not use_kernel:
        return ("chunked prefill: GSPMD unsharded admission under mesh "
                "(kernel off: not on TPU)")
    from repro.dist.sharding import prefill_plan
    plan, reason = prefill_plan(cfg, mesh, chunk_len)
    if plan is not None:
        heads = (f"kv_heads over {plan.kv_head_axis!r}"
                 if plan.kv_head_axis else "kv_heads replicated")
        return ("chunked prefill: ring attention shard_map'd over "
                f"{plan.seq_axis!r} ({plan.n_shards} sequence shards, "
                f"{heads})")
    return ("chunked prefill: GSPMD unsharded admission FALLBACK under "
            f"mesh — {reason}")


def _flat_axis_index(mesh, axes):
    """Linear shard index over (possibly several) mesh axes, major-first —
    matches how GSPMD linearizes a dim sharded over an axis tuple."""
    flat = axes if isinstance(axes, tuple) else (axes,)
    idx = None
    for a in flat:
        i = jax.lax.axis_index(a)
        idx = i if idx is None else idx * mesh.shape[a] + i
    return idx


def _sharded_write_attend(q, k_store, v_store, position, active,
                          cache: PagedKVCache, layer, mesh, plan, *,
                          window: int, kv_scale: float, cap: float,
                          interpret: bool):
    """ONE shard_map region: slot-affinity dynamic cache write + the fused
    Pallas kernel, zero collectives.

    Under the slot-affinity layout (``serve.pages``: slot ``s``'s pages all
    live in its shard's contiguous page range) every device holds exactly
    the pages its slots' block tables reference, so inside the region the
    global page ids rebase to local ones (``pid - shard * chunk``; the 0
    sentinel maps to the shard's local null page 0) and both the
    dynamic-index ``.at[page, offset].set`` write — illegal under GSPMD on a
    sharded page dim — and the scalar-prefetch kernel grid become plain
    single-device programs per shard. Inactive rows write into the local
    null page (never read). q: (B, G, R, hd); k_store/v_store: (B, G, hd)
    at cache dtype; the pools stacked over layers, written and read at
    ``layer`` (the layer dim is unsharded). Returns (o (B, G, R, hd), new
    PagedKVCache).
    """
    from jax.sharding import PartitionSpec as P
    from repro.kernels.paged_attention import paged_attention_impl
    b, g = plan.batch_axes, plan.kv_head_axis
    _, n_pages, Pg = cache.ppos.shape
    chunk = n_pages // plan.n_shards

    def inner(q_l, k_l, v_l, pos_l, act_l, kp_l, vp_l, ppos_l, block_l,
              layer_l):
        base = _flat_axis_index(mesh, b) * chunk
        lblock = jnp.where(block_l == 0, 0, block_l - base)
        phys = jnp.take_along_axis(lblock, (pos_l // Pg)[:, None],
                                   axis=1)[:, 0]
        tgt = jnp.where(act_l, phys, 0)
        off = pos_l % Pg
        nkp = kp_l.at[layer_l, tgt, off].set(k_l)
        nvp = vp_l.at[layer_l, tgt, off].set(v_l)
        nppos = ppos_l.at[layer_l, tgt, off].set(pos_l)
        o = paged_attention_impl(q_l, nkp, nvp, nppos, lblock, pos_l,
                                 layer_l, window=window, kv_scale=kv_scale,
                                 cap=cap, interpret=interpret)
        return o, nkp, nvp, nppos

    q_spec = P(b, g, None, None)
    kv_spec = P(None, b, None, g, None)
    ppos_spec = P(None, b, None)
    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(q_spec, P(b, g, None), P(b, g, None), P(b), P(b),
                  kv_spec, kv_spec, ppos_spec, P(b, None), P()),
        out_specs=(q_spec, kv_spec, kv_spec, ppos_spec),
        check_vma=False)
    o, nkp, nvp, nppos = fn(q, k_store, v_store, position, active,
                            cache.kp, cache.vp, cache.ppos,
                            cache.block[layer], layer)
    return o, PagedKVCache(nkp, nvp, nppos, cache.block)


def paged_decode_attention(params, x, position, cache: PagedKVCache,
                           cfg: ModelConfig, *, layer, window: int = 0,
                           kv_scale: float = 0.0, active=None,
                           use_kernel: Optional[bool] = None,
                           interpret: bool = False,
                           dyn_scatter: bool = False, mesh=None):
    """One-token decode against the stacked paged pool at ``layer``.
    x: (B,1,D); position: (B,).

    The new K/V entry scatters into the slot's private tail page (host-side
    allocation guarantees it is mapped and unshared before the step runs);
    attention reads every mapped page through the block table masked by
    position/window — the paged sibling of ``decode_attention``.

    ``active`` (B,) bool masks the cache WRITE per slot: rows of a decode
    batch whose slot has no live request (e.g. an admission prefilling in
    the background between decode steps) must not scatter garbage into
    their mapped pages or ppos rows. Inactive rows' outputs are garbage the
    engine never reads.

    ``use_kernel`` selects the fused Pallas kernel
    (``kernels.paged_attention``): pages stream HBM->VMEM in place via the
    block table with online-softmax accumulation — O(live pages) traffic.
    Defaults to the kernel on TPU; the ``_gather_pages`` + ``_sdpa`` path
    below is the interpret/reference fallback (and the GSPMD path for
    sharded pools).

    ``dyn_scatter`` replaces the one-hot masked write (O(n_pages * P) work
    per entry) with a dynamic-index ``.at[page, offset].set`` — O(1) per
    entry. Safe ONLY for unsharded pools: under GSPMD a dynamic scatter on
    a partitioned page dim lowers to all-gather traffic, which is exactly
    what the one-hot form avoids. Inactive rows are redirected to the null
    page instead of suppressed, an equivalent no-op (page 0 is never read).

    ``mesh`` + kernel requested: when ``dist.sharding.paged_decode_plan``
    finds a slot-affinity layout, write AND kernel both run inside ONE
    ``shard_map`` region (``_sharded_write_attend``) — each device's kernel
    invocation prefetches only its shard's pages, so multi-device decode
    runs at single-device speed per shard. Otherwise the gather fallback
    below is taken and ``_warn_gather`` says so (once per reason).
    """
    from repro.kernels import ops as kops
    from repro.kernels.paged_attention import paged_attention
    B, one, D = x.shape
    hd = cfg.resolved_head_dim
    G, R = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    q = _split_heads(x @ params["wq"], cfg.n_heads, hd)
    k = _split_heads(x @ params["wk"], G, hd)
    v = _split_heads(x @ params["wv"], G, hd)
    q = apply_rope(q, position[:, None], cfg.rope_theta)
    k = apply_rope(k, position[:, None], cfg.rope_theta)
    if kv_scale:
        k_store = quantize_kv(k, kv_scale)
        v_store = quantize_kv(v, kv_scale)
    else:
        k_store = k.astype(cache.kp.dtype)
        v_store = v.astype(cache.vp.dtype)
    _, n_pages, P = cache.ppos.shape
    block = cache.block[layer]
    if use_kernel is None:
        use_kernel = kops._on_tpu()
    if mesh is not None:
        if use_kernel:
            from repro.dist.sharding import paged_decode_plan
            plan, reason = paged_decode_plan(cfg, mesh, B, n_pages)
        else:
            plan, reason = None, "use_kernel=False (kernel disabled)"
        if plan is not None:
            DISPATCH_COUNTS["kernel_sharded"] += 1
            act = (active if active is not None
                   else jnp.ones((B,), jnp.bool_))
            o, new_cache = _sharded_write_attend(
                q[:, 0].reshape(B, G, R, hd), k_store[:, 0], v_store[:, 0],
                position, act, cache, layer, mesh, plan, window=window,
                kv_scale=kv_scale, cap=cfg.attn_softcap, interpret=interpret)
            return o.reshape(B, 1, cfg.q_dim) @ params["wo"], new_cache
        DISPATCH_COUNTS["gather_mesh"] += 1
        _warn_gather(reason)
        use_kernel = False
    else:
        DISPATCH_COUNTS["kernel_single" if use_kernel
                        else "gather_single"] += 1
    phys = jnp.take_along_axis(block, (position // P)[:, None],
                               axis=1)[:, 0]              # (B,)
    if dyn_scatter:
        tgt = phys if active is None else jnp.where(active, phys, 0)
        off = position % P
        nkp = cache.kp.at[layer, tgt, off].set(k_store[:, 0])
        nvp = cache.vp.at[layer, tgt, off].set(v_store[:, 0])
        nppos = cache.ppos.at[layer, tgt, off].set(position)
    else:
        sel = ((jnp.arange(n_pages)[None, :, None] == phys[:, None, None])
               & (jnp.arange(P)[None, None, :]
                  == (position % P)[:, None, None]))
        if active is not None:
            sel &= active[:, None, None]
        write = sel.any(axis=0)
        nkp = _page_scatter(sel, write, cache.kp, layer, k_store[:, 0])
        nvp = _page_scatter(sel, write, cache.vp, layer, v_store[:, 0])
        nppos = _page_scatter(sel, write, cache.ppos, layer, position)
    new_cache = PagedKVCache(nkp, nvp, nppos, cache.block)

    if use_kernel:
        qk = q[:, 0].reshape(B, G, R, hd)
        o = paged_attention(qk, nkp, nvp, nppos, block, position, layer,
                            window=window, kv_scale=kv_scale,
                            cap=cfg.attn_softcap, interpret=interpret)
        return o.reshape(B, 1, cfg.q_dim) @ params["wo"], new_cache

    kk, vv, _, valid = _gather_pages(new_cache, layer, block,
                                     position[:, None], window=window)
    dq = (lambda a: dequantize_kv(a, q.dtype, kv_scale)) if kv_scale else \
        (lambda a: a.astype(q.dtype))
    qg = q.reshape(B, 1, G, R, hd)
    o = _sdpa(qg, dq(kk), dq(vv), mask=valid[:, None, None],
              cap=cfg.attn_softcap)
    return _merge(o, B, 1, cfg.q_dim) @ params["wo"], new_cache


def paged_chunk_attention(params, x, positions, cache: PagedKVCache,
                          cfg: ModelConfig, slot, *, layer, window: int = 0,
                          kv_scale: float = 0.0, valid=None,
                          dyn_scatter: bool = False,
                          mesh=None, use_kernel: Optional[bool] = None,
                          interpret: bool = False):
    """C-token prompt-chunk step for ONE slot of the stacked paged pool at
    ``layer`` (chunked admission). x: (1,C,D); positions: (1,C); ``slot``
    is a traced scalar — one executable of chunk length C serves every slot
    and every chunk.

    Scatters the chunk's K/V into the slot's (pre-allocated, private) pages,
    then attends over every mapped page — the chunk's own entries included,
    causally masked by position. Prefix-shared pages are simply already
    present in the block row; chunks the engine skipped on a prefix hit were
    never run. ``valid`` (C,) bool (None = every row) marks the rows whose
    K/V and position are written: the others, the pad rows of a chunk
    shorter than C, write to the never-read null page (``dyn_scatter``) or
    are dropped from the one-hot mask, as inactive decode rows are.

    ``mesh`` + kernel requested: when ``dist.sharding.prefill_plan`` finds a
    sequence layout, the attend runs in ``kernels.ring_attention``. The
    slot's pages live on ONE shard under slot affinity, so the block-table
    gather stays *outside* the ring region — GSPMD moves each mapped page
    once into the ring's sequence-sharded layout (the per-shard rebase: each
    shard holds a contiguous slice of the gathered context and its absolute
    positions) — and the dominant O(C x L) attention compute/bytes then
    split 1/n_shards per device. Unmapped block entries fold into the
    position lane as -1 before the ring, which masks them identically to
    ``_gather_pages``. Fallback is the whole-chunk gather + ``_sdpa``.
    """
    from repro.dist.annotate import constrain_replicated
    B, C, D = x.shape
    hd = cfg.resolved_head_dim
    G, R = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    # gather the chunk Q/K/V before rope: the 0.4.x partitioner miscompiles
    # split+concat over a TP-sharded head_dim (wrong values, not just slow);
    # these are only a few tokens wide, so the gather is cheap
    q = constrain_replicated(_split_heads(x @ params["wq"], cfg.n_heads, hd))
    k = constrain_replicated(_split_heads(x @ params["wk"], G, hd))
    v = constrain_replicated(_split_heads(x @ params["wv"], G, hd))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if kv_scale:
        k_store = quantize_kv(k, kv_scale)
        v_store = quantize_kv(v, kv_scale)
    else:
        k_store = k.astype(cache.kp.dtype)
        v_store = v.astype(cache.vp.dtype)
    _, n_pages, P = cache.ppos.shape
    brow = cache.block[layer, slot]                       # (M,)
    pos_c = positions[0]                                  # (C,)
    phys = jnp.take(brow, pos_c // P)                     # (C,)
    if dyn_scatter:
        # dynamic-index write (unsharded pools only — see
        # paged_decode_attention): valid rows' positions are distinct, so
        # their targets never collide; pad rows collide on the null page
        tgt = phys if valid is None else jnp.where(valid, phys, 0)
        off = pos_c % P
        nkp = cache.kp.at[layer, tgt, off].set(k_store[0])
        nvp = cache.vp.at[layer, tgt, off].set(v_store[0])
        nppos = cache.ppos.at[layer, tgt, off].set(pos_c)
    else:
        sel = ((jnp.arange(n_pages)[None, :, None] == phys[:, None, None])
               & (jnp.arange(P)[None, None, :]
                  == (pos_c % P)[:, None, None]))
        if valid is not None:
            sel &= valid[:, None, None]
        write = sel.any(axis=0)
        nkp = _page_scatter(sel, write, cache.kp, layer, k_store[0])
        nvp = _page_scatter(sel, write, cache.vp, layer, v_store[0])
        nppos = _page_scatter(sel, write, cache.ppos, layer, pos_c)
    new_cache = PagedKVCache(nkp, nvp, nppos, cache.block)

    plan, _ = _prefill_ring_plan(cfg, mesh, C, use_kernel)
    if plan is not None:
        from repro.kernels.ring_attention import ring_chunk_attention
        M = brow.shape[0]
        gk = nkp[layer, brow[None]].reshape(B, M * P, G, hd)
        gv = nvp[layer, brow[None]].reshape(B, M * P, G, hd)
        gpos = nppos[layer, brow[None]].reshape(B, M * P)
        mapped = jnp.repeat(brow[None] != 0, P, axis=1)
        kv_pos = jnp.where(mapped, gpos, -1)
        o = ring_chunk_attention(q.reshape(B, C, G, R, hd), gk, gv,
                                 positions, kv_pos, mesh=mesh, plan=plan,
                                 window=window, cap=cfg.attn_softcap,
                                 kv_scale=kv_scale, interpret=interpret)
        return _merge(o, B, C, cfg.q_dim) @ params["wo"], new_cache

    kk, vv, _, valid = _gather_pages(new_cache, layer, brow[None], positions,
                                     window=window)
    dq = (lambda a: dequantize_kv(a, q.dtype, kv_scale)) if kv_scale else \
        (lambda a: a.astype(q.dtype))
    qg = q.reshape(B, C, G, R, hd)
    o = _sdpa(qg, dq(kk), dq(vv), mask=valid[:, None, None],
              cap=cfg.attn_softcap)
    return _merge(o, B, C, cfg.q_dim) @ params["wo"], new_cache


# ------------------------------------------------------------ latent (MLA) --
# DeepSeek-V2/V3 latent attention without a q latent: q comes straight from
# x as H heads of (nope + rope) dims; one kv_lora_rank latent per token
# (RMS-normed) expands into each head's nope key and value, and one shared
# rotary key joins every head's key. The cache keeps only [latent | rotary
# key] per token; decode runs absorbed (q_nope W_UK attends over the
# latents as one KV head, the latent output goes through W_UV).

def mla_specs(cfg: ModelConfig):
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.resolved_head_dim
    r, hr = cfg.kv_lora_rank, cfg.rope_dim
    return {
        "wq": ParamSpec((d, H * (hd + hr)), ("embed", "q_heads")),
        "wkv_a": ParamSpec((d, r + hr), ("embed", None)),
        "kv_norm": ParamSpec((r,), (None,), init="ones"),
        "wk_b": ParamSpec((r, H * hd), (None, "q_heads")),
        "wv_b": ParamSpec((r, H * hd), (None, "q_heads")),
        "wo": ParamSpec((H * hd, d), ("q_heads", "embed")),
    }


def _mla_scale(cfg: ModelConfig) -> float:
    return (cfg.resolved_head_dim + cfg.rope_dim) ** -0.5


def _mla_project(params, x, positions, cfg: ModelConfig):
    """x: (B,S,D) -> (q_nope (B,S,H,hd), roped q_pe (B,S,H,hr), cache entry
    (B,S,r+hr) = [normed latent | roped shared key])."""
    B, S, _ = x.shape
    H, hd, r = cfg.n_heads, cfg.resolved_head_dim, cfg.kv_lora_rank
    q = (x @ params["wq"]).reshape(B, S, H, -1)
    q_pe = apply_rope(q[..., hd:], positions, cfg.rope_theta)
    kv = x @ params["wkv_a"]
    c = rms_norm(kv[..., :r], params["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(kv[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    return q[..., :hd], q_pe, jnp.concatenate([c, k_pe], axis=-1)


def mla_attention(params, x, positions, cfg: ModelConfig, *,
                  q_chunk: int = 0, kv_keep_stride: int = 1):
    """Full-sequence causal MLA in the expanded form: each head's key is
    [latent W_UK | shared rotary key], its value latent W_UV."""
    B, S, _ = x.shape
    H, hd, r = cfg.n_heads, cfg.resolved_head_dim, cfg.kv_lora_rank
    q_nope, q_pe, ent = _mla_project(params, x, positions, cfg)
    c, k_pe = ent[..., :r], ent[..., r:]
    k_nope = (c @ params["wk_b"]).reshape(B, S, H, hd)
    v = (c @ params["wv_b"]).reshape(B, S, H, hd)
    q = jnp.concatenate([q_nope, q_pe], -1)[:, :, :, None]   # (B,S,H,1,.)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe[:, :, None], (B, S, H, cfg.rope_dim))], -1)
    o = _causal_chunked(q, k, v, q_chunk=q_chunk or default_q_chunk(S),
                        kv_keep_stride=kv_keep_stride, cap=0.0)
    return o.reshape(B, S, H * hd) @ params["wo"]


def _absorb_q(params, q_nope, q_pe, cfg: ModelConfig):
    """Queries in the latent space: [q_nope W_UK^T | q_pe] per head."""
    H, hd, r = cfg.n_heads, cfg.resolved_head_dim, cfg.kv_lora_rank
    q_lat = jnp.einsum("...hd,rhd->...hr", q_nope,
                       params["wk_b"].reshape(r, H, hd))
    return jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1)


def _latent_out(params, o_lat, cfg: ModelConfig):
    """Latent attention outputs (..., H, r) through W_UV and the output
    projection."""
    H, hd, r = cfg.n_heads, cfg.resolved_head_dim, cfg.kv_lora_rank
    o = jnp.einsum("...hr,rhd->...hd", o_lat,
                   params["wv_b"].reshape(r, H, hd))
    return o.reshape(o.shape[:-2] + (H * hd,)) @ params["wo"]


class PagedLatentCache(NamedTuple):
    """Paged latent (MLA) cache: one [latent | rotary key] entry per
    position in a shared physical page pool, block tables as
    ``PagedKVCache`` (physical page 0 is the null page). The attention
    functions below take it stacked over layers (leading axis) with the
    layer's index: the layer scan carries the whole stack, so entries are
    written in place and the kernel reads its layer without a copy."""
    cp: jax.Array         # (n_pages, page_size, W) physical page pool,
                          # W = r + hr rounded up to 128 lanes
    ppos: jax.Array       # (n_pages, page_size) absolute positions, -1 empty
    block: jax.Array      # (B, max_pages) int32 physical page ids


def _lane_pad(x, cfg: ModelConfig):
    """Zero-pad the last dim of entries (or of latent-space queries) from
    ``cache_width`` to a whole number of 128-lane tiles: a pool whose last
    dim is not one is laid out pages-minor by the TPU's default layout, and
    every call would then copy the pool to the layout the writes and the
    kernel use. The pad columns are zero in entries and queries alike."""
    pad = -cfg.cache_width % 128
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def init_paged_latent_cache(cfg: ModelConfig, batch: int, n_pages: int,
                            page_size: int, max_pages: int,
                            dtype=jnp.bfloat16,
                            quantized: bool = False) -> PagedLatentCache:
    width = -(-cfg.cache_width // 128) * 128
    return PagedLatentCache(
        cp=jnp.zeros((n_pages, page_size, width),
                     jnp.int8 if quantized else dtype),
        ppos=jnp.full((n_pages, page_size), -1, jnp.int32),
        block=jnp.zeros((batch, max_pages), jnp.int32))


def _latent_write(cache: PagedLatentCache, layer, phys, positions, ent, *,
                  keep):
    """Write entries ent (N, W) of stacked layer ``layer`` at absolute
    ``positions`` (N,) into pages ``phys`` (N,); rows with ``keep`` False
    (None = all) go to the never-read null page. An indexed write: the
    latent pool is single-device (no GSPMD hazard)."""
    P = cache.ppos.shape[-1]
    tgt = phys if keep is None else jnp.where(keep, phys, 0)
    off = positions % P
    return cache._replace(cp=cache.cp.at[layer, tgt, off].set(ent),
                          ppos=cache.ppos.at[layer, tgt, off].set(positions))


def _latent_attend(q_abs, cache: PagedLatentCache, layer, block,
                   q_positions, *, dv: int, scale: float, kv_scale: float):
    """Absorbed attention over a block table's latent pages of stacked
    layer ``layer`` (the reference of the latent kernel, and the admission
    chunk's attention). q_abs: (B, C, H, W); block: (B, M); q_positions:
    (B, C). Returns (B, C, H, dv) latent outputs."""
    B, M = block.shape
    P = cache.ppos.shape[-1]
    ent = cache.cp[layer, block].reshape(B, M * P, -1)
    ent = dequantize_kv(ent, q_abs.dtype, kv_scale) if kv_scale else \
        ent.astype(q_abs.dtype)
    gpos = cache.ppos[layer, block].reshape(B, M * P)
    valid = jnp.repeat(block != 0, P, axis=1)[:, None, :] \
        & (gpos[:, None, :] >= 0) & (gpos[:, None, :] <= q_positions[..., None])
    s = jnp.einsum("bchw,btw->bhct", q_abs, ent,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q_abs.dtype)
    return jnp.einsum("bhct,btv->bchv", p, ent[..., :dv])


def _latent_mesh_check(mesh) -> None:
    if mesh is not None:
        raise NotImplementedError(
            "latent (MLA) attention serves from a single-device page pool")


def paged_latent_decode_attention(params, x, position,
                                  cache: PagedLatentCache, cfg: ModelConfig,
                                  *, layer, kv_scale: float = 0.0,
                                  active=None,
                                  use_kernel: Optional[bool] = None,
                                  interpret: bool = False, mesh=None):
    """One-token MLA decode against the stacked latent pool at ``layer``,
    absorbed: the new entry goes to the slot's page (inactive rows to the
    null page, as in ``paged_decode_attention``), then every head attends
    over the latents through the fused ``paged_latent_decode`` kernel
    (``use_kernel``, default on TPU) or the gather reference."""
    from repro.kernels import ops as kops
    from repro.kernels.paged_attention import paged_latent_attention
    _latent_mesh_check(mesh)
    B = x.shape[0]
    P = cache.ppos.shape[-1]
    block = cache.block[layer]
    q_nope, q_pe, ent = _mla_project(params, x, position[:, None], cfg)
    ent = _lane_pad(ent[:, 0], cfg)
    store = quantize_kv(ent, kv_scale) if kv_scale else \
        ent.astype(cache.cp.dtype)
    phys = jnp.take_along_axis(block, (position // P)[:, None], axis=1)[:, 0]
    new_cache = _latent_write(cache, layer, phys, position, store,
                              keep=active)
    q_abs = _lane_pad(_absorb_q(params, q_nope[:, 0], q_pe[:, 0], cfg),
                      cfg)                                      # (B,H,W)
    r, scale = cfg.kv_lora_rank, _mla_scale(cfg)
    if use_kernel is None:
        use_kernel = kops._on_tpu()
    DISPATCH_COUNTS["latent_kernel" if use_kernel else "latent_gather"] += 1
    if use_kernel:
        o_lat = paged_latent_attention(q_abs, new_cache.cp, new_cache.ppos,
                                       block, position, layer, dv=r,
                                       scale=scale, kv_scale=kv_scale,
                                       interpret=interpret)
    else:
        o_lat = _latent_attend(q_abs[:, None], new_cache, layer, block,
                               position[:, None], dv=r, scale=scale,
                               kv_scale=kv_scale)[:, 0]
    return _latent_out(params, o_lat, cfg).reshape(B, 1, -1), new_cache


def paged_latent_chunk_attention(params, x, positions,
                                 cache: PagedLatentCache, cfg: ModelConfig,
                                 slot, *, layer, kv_scale: float = 0.0,
                                 valid=None, mesh=None):
    """C-token MLA prompt chunk for ONE slot of the stacked latent pool at
    ``layer`` (chunked admission): the chunk's entries go to the slot's
    pages (``valid`` as in ``paged_chunk_attention``), then the chunk
    attends, absorbed, over every mapped latent page, its own entries
    included."""
    _latent_mesh_check(mesh)
    P = cache.ppos.shape[-1]
    q_nope, q_pe, ent = _mla_project(params, x, positions, cfg)
    ent = _lane_pad(ent[0], cfg)
    store = quantize_kv(ent, kv_scale) if kv_scale else \
        ent.astype(cache.cp.dtype)
    brow = cache.block[layer, slot]                       # (M,)
    pos_c = positions[0]
    new_cache = _latent_write(cache, layer, jnp.take(brow, pos_c // P),
                              pos_c, store, keep=valid)
    q_abs = _lane_pad(_absorb_q(params, q_nope, q_pe, cfg), cfg)  # (1,C,H,W)
    o_lat = _latent_attend(q_abs, new_cache, layer, brow[None], positions,
                           dv=cfg.kv_lora_rank, scale=_mla_scale(cfg),
                           kv_scale=kv_scale)
    return _latent_out(params, o_lat, cfg), new_cache
