"""Pallas TPU kernel: fused paged-attention decode (vLLM-style).

One query token per batch slot attends over its block table's K/V pages
**in place**: the grid runs (slot, logical_page) with the page dim
innermost (sequential) and every KV head of a page in one step, the block
table and per-slot positions are scalar-prefetched so each page's BlockSpec
index map streams the *physical* page HBM -> VMEM directly, and an
online-softmax accumulator in VMEM scratch folds pages as they arrive. The
dense ``(B, S_max, G, hd)`` gather buffer of the reference path never
exists, so per-step decode HBM traffic scales with LIVE pages instead of
slots x max_len.

Dead traffic is skipped at two levels:

* **index map** — unmapped block entries already point at the reserved null
  page 0; the map also redirects pages wholly past the query position
  (speculatively-reserved decode pages from grouped admission) and, with
  ``window`` > 0, pages wholly below the local-attention band. Consecutive
  grid steps that map the same page elide the re-fetch, so skipped pages
  cost (at most) one null-page DMA.
* **``@pl.when`` body guard** — null/out-of-band/future pages skip the MXU
  work entirely; partial pages are masked per-entry by the page's ``ppos``
  row (position -1 = empty, plus causal/window masking), exactly mirroring
  the reference ``models.attention._gather_pages`` validity.

``kv_scale`` > 0 fuses int8 -> fp dequantization into the page load (the
``kv_quant`` serving knob): quantized K/V pages stream as int8 and are
scaled in VMEM, never round-tripping through an fp32 HBM buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(block_ref, pos_ref, q_ref, k_ref, v_ref, ppos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page: int, n_m: int, n_kv: int,
            window: int, kv_scale: float, cap: float, scale: float):
    b = pl.program_id(0)
    m = pl.program_id(1)          # logical page (sequential)

    @pl.when(m == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pid = block_ref[b, m]
    pos = pos_ref[b]
    run = pid != 0                               # unmapped -> null page
    run &= m * page <= pos                       # page starts past the query
    if window:
        run &= (m + 1) * page - 1 > pos - window  # wholly below the band

    @pl.when(run)
    def _body():
        kv_pos = ppos_ref[0]                         # (1, P)
        valid = (kv_pos >= 0) & (kv_pos <= pos)
        if window:
            valid &= kv_pos > pos - window
        for g in range(n_kv):                        # static: one KV head
            q = q_ref[0, g].astype(jnp.float32)          # (R, hd)
            k = k_ref[0, :, g, :].astype(jnp.float32)    # (P, hd)
            v = v_ref[0, :, g, :].astype(jnp.float32)
            if kv_scale:                                 # fused int8 dequant
                k = k * kv_scale
                v = v * kv_scale
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if cap:
                s = cap * jnp.tanh(s / cap)
            s = jnp.where(valid, s, NEG_INF)             # (R, P)
            m_prev, l_prev = m_ref[g], l_ref[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[g] = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
            m_ref[g] = m_new
            acc_ref[g] = (acc_ref[g] * alpha
                          + jax.lax.dot_general(
                              p, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32))

    @pl.when(m == n_m - 1)
    def _finish():
        # all-masked slots (inactive decode rows) leave l == 0: emit zeros
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_impl(q, kp, vp, ppos, block, position, *, window: int = 0,
                         kv_scale: float = 0.0, cap: float = 0.0,
                         interpret: bool = False):
    """Fused paged decode attention (unjitted body).

    q: (B, G, R, hd) — current token's queries, grouped by KV head;
    kp/vp: (n_pages, P, G, hd) physical page pools (int8 when ``kv_scale``);
    ppos: (n_pages, P) absolute positions (-1 empty); block: (B, M) int32
    physical page ids (0 = unmapped); position: (B,) absolute query position.
    Returns (B, G, R, hd) in q.dtype.

    One grid step streams a whole page, every KV head at once: the TPU
    compiler tiles the last two block dims, which must be (8, 128)-aligned
    or span the array, so a page block is (1, P, G, hd) and the ``ppos``
    row travels as a (1, 1, P) block of a (n_pages, 1, P) view.

    Use ``paged_attention`` (the jitted wrapper) from op-level code; this
    raw body exists so ``models.attention`` can call the kernel INSIDE a
    ``shard_map`` region with per-shard (rebased) block tables — a nested
    jit there would re-trace per shard for nothing.
    """
    B, G, R, hd = q.shape
    n_pages, P = ppos.shape
    M = block.shape[1]
    block = block.astype(jnp.int32)
    position = position.astype(jnp.int32)

    def _qo_map(b, m, block_ref, pos_ref):
        return (b, 0, 0, 0)

    def _page_map(b, m, block_ref, pos_ref):
        pid = block_ref[b, m]
        # redirect dead pages to the null page: the fetch aliases page 0
        # (elided when consecutive) instead of streaming a page the body
        # guard would ignore anyway. Dead = wholly past the query position
        # (grouped admission speculatively maps a request's projected decode
        # pages up front — still empty, never attended) or, with a window,
        # wholly below the local-attention band.
        dead = m * P > pos_ref[b]
        if window:
            dead |= (m + 1) * P - 1 <= pos_ref[b] - window
        return jnp.where(dead, 0, pid)

    def _kv_map(b, m, block_ref, pos_ref):
        return (_page_map(b, m, block_ref, pos_ref), 0, 0, 0)

    def _ppos_map(b, m, block_ref, pos_ref):
        return (_page_map(b, m, block_ref, pos_ref), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, G, R, hd), _qo_map),
            pl.BlockSpec((1, P, G, hd), _kv_map),
            pl.BlockSpec((1, P, G, hd), _kv_map),
            pl.BlockSpec((1, 1, P), _ppos_map),
        ],
        out_specs=pl.BlockSpec((1, G, R, hd), _qo_map),
        scratch_shapes=[
            pltpu.VMEM((G, R, 1), jnp.float32),
            pltpu.VMEM((G, R, 1), jnp.float32),
            pltpu.VMEM((G, R, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, page=P, n_m=M, n_kv=G, window=window, kv_scale=kv_scale,
        cap=cap, scale=hd ** -0.5)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block, position, q, kp, vp, ppos.reshape(n_pages, 1, P))


paged_attention = functools.partial(jax.jit, static_argnames=(
    "window", "kv_scale", "cap", "interpret"))(paged_attention_impl)


def page_hbm_bytes(page_size: int, n_kv_heads: int, head_dim: int, *,
                   kv_bytes: int = 4) -> int:
    """HBM bytes one live page streams through the fused kernel: K + V
    entries at the cache dtype width plus the int32 ``ppos`` row."""
    return 2 * page_size * n_kv_heads * head_dim * kv_bytes + 4 * page_size


def decode_hbm_bytes(live_pages: int, page_size: int, n_kv_heads: int,
                     head_dim: int, *, kv_bytes: int = 4, batch: int = 1,
                     n_heads: int = 0, q_bytes: int = 4,
                     max_pages: int = 0) -> int:
    """Per-step attention HBM bytes of the fused paged decode: every live
    page streamed once (each KV head's slice exactly once), plus the query/
    output vectors and the scalar-prefetched tables (the full (B, max_pages)
    block table + the (B,) positions). This is the kernel's cost model —
    O(live pages), not O(slots x max_len) — used by the explorer's decode
    pricing and the kernel benchmark's bytes-moved accounting."""
    nh = n_heads or n_kv_heads
    qo = 2 * batch * nh * head_dim * q_bytes
    tables = batch * 4 * (max_pages + 1)        # block rows + positions, int32
    return live_pages * page_hbm_bytes(page_size, n_kv_heads, head_dim,
                                       kv_bytes=kv_bytes) + qo + tables


def sharded_decode_hbm_bytes(live_pages: int, page_size: int,
                             n_kv_heads: int, head_dim: int, *,
                             n_shards: int = 1, kv_bytes: int = 4,
                             batch: int = 1, n_heads: int = 0,
                             q_bytes: int = 4, max_pages: int = 0) -> int:
    """PER-DEVICE attention HBM bytes of the shard_map'd fused decode under
    slot-affinity placement: each device runs the kernel over only its own
    slots' block tables, so it streams ceil(live/n_shards) pages for
    ceil(batch/n_shards) query rows (balanced placement — the allocator pins
    slot s to shard s*n_shards//batch_slots). The per-device traffic scales
    with live pages per shard, NOT slots x max_len — the acceptance metric
    of the sharded kernel path."""
    return decode_hbm_bytes(
        -(-live_pages // n_shards), page_size, n_kv_heads, head_dim,
        kv_bytes=kv_bytes, batch=-(-batch // n_shards), n_heads=n_heads,
        q_bytes=q_bytes, max_pages=max_pages)
