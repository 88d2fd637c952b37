"""Pallas TPU kernel: fused paged-attention decode (vLLM-style).

One query token per batch slot attends over its block table's K/V pages
**in place**: the grid runs (slot, logical_page) with the page dim
innermost (sequential) and every KV head of a page in one step, the block
table and per-slot positions are scalar-prefetched so each page's BlockSpec
index map streams the *physical* page HBM -> VMEM directly, and an
online-softmax accumulator in VMEM scratch folds pages as they arrive. The
dense ``(B, S_max, G, hd)`` gather buffer of the reference path never
exists, so per-step decode HBM traffic scales with LIVE pages instead of
slots x max_len. The pools come stacked over layers, as the layer scan
carries them, and the layer index is scalar-prefetched beside the tables:
the index maps pick the layer's pages out of the stack, so no layer's pool
is sliced out for the kernel.

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

A latent mode (``paged_latent_attention``, MLA's absorbed decode) reads a
pool of one [latent | rotary key] entry per position: every query head
attends over it as one KV head, and the value is the entry's leading
latent columns of the same entries. It walks live pages only: the pool
stays in HBM, and one kernel call runs through every slot's blocks of
``128 // P`` pages up to the block holding its query position, copying
each block's live pages into a double-buffered VMEM block itself (the
next block's copies, the next slot's first among them, start before the
current block is attended). Dead blocks cost no step, and dead pages of
the last block are never fetched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(layer_ref, block_ref, pos_ref, q_ref, k_ref, v_ref, ppos_ref,
            o_ref, m_ref, l_ref, acc_ref, *, page: int, n_m: int, n_kv: int,
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


def paged_attention_impl(q, kp, vp, ppos, block, position, layer, *,
                         window: int = 0, kv_scale: float = 0.0,
                         cap: float = 0.0, interpret: bool = False):
    """Fused paged decode attention (unjitted body).

    q: (B, G, R, hd) — current token's queries, grouped by KV head;
    kp/vp: (L, n_pages, P, G, hd) physical page pools stacked over layers
    (int8 when ``kv_scale``), of which ``layer`` (a traced scalar) is read
    — the layer scan hands the kernel the whole stack, so no layer's pool
    is copied for it; ppos: (L, n_pages, P) absolute positions (-1 empty);
    block: (B, M) int32 the layer's physical page ids (0 = unmapped);
    position: (B,) absolute query position. Returns (B, G, R, hd) in
    q.dtype.

    One grid step streams a whole page, every KV head at once: the TPU
    compiler tiles the last two block dims, which must be (8, 128)-aligned
    or span the array, so a page block is (P, G, hd) of layer ``layer``
    (the layer dim squeezed) and the ``ppos`` row travels as a (1, 1, P)
    block of the layer's (n_pages, 1, P) view. That view is a copy on the
    TPU (the row-per-page tiling differs from the pool's): it is taken of
    the one layer's positions, n_pages x P int32, since a view of the
    whole stack would copy every layer's at each layer.

    Use ``paged_attention`` (the jitted wrapper) from op-level code; this
    raw body exists so ``models.attention`` can call the kernel INSIDE a
    ``shard_map`` region with per-shard (rebased) block tables — a nested
    jit there would re-trace per shard for nothing.
    """
    B, G, R, hd = q.shape
    n_pages, P = ppos.shape[1:]
    M = block.shape[1]
    block = block.astype(jnp.int32)
    position = position.astype(jnp.int32)

    def _qo_map(b, m, layer_ref, block_ref, pos_ref):
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

    def _kv_map(b, m, layer_ref, block_ref, pos_ref):
        return (layer_ref[0], _page_map(b, m, block_ref, pos_ref), 0, 0, 0)

    def _ppos_map(b, m, layer_ref, block_ref, pos_ref):
        return (_page_map(b, m, block_ref, pos_ref), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, G, R, hd), _qo_map),
            pl.BlockSpec((None, 1, P, G, hd), _kv_map),
            pl.BlockSpec((None, 1, P, G, hd), _kv_map),
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
        name="paged_attention_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), block, position, q, kp, vp,
      jax.lax.dynamic_index_in_dim(ppos, layer, 0, keepdims=False)
      .reshape(n_pages, 1, P))


paged_attention = functools.partial(jax.jit, static_argnames=(
    "window", "kv_scale", "cap", "interpret"))(paged_attention_impl)


# ---- latent mode (MLA): one KV "head" per token, its latent as the value

def _latent_kernel(layer_ref, block_ref, pos_ref, q_ref, cp_ref, kvp_ref,
                   o_ref, c_buf, p_buf, m_ref, l_ref, acc_ref, sem, *,
                   page: int, ppb: int, n_m: int, dv: int, kv_scale: float,
                   scale: float):
    B = q_ref.shape[0]
    bk = ppb * page                      # tokens of one compute block
    n_blk = kvp_ref.shape[1]
    layer = layer_ref[0]

    def n_walk(b):
        # blocks up to the one holding the query position; at least one,
        # so every slot's output is written (a slot with no live page
        # emits 0)
        return jnp.clip(pos_ref[b] // bk + 1, 1, n_blk)

    def copies(b, k, slot):
        """Block k of slot b into buffer ``slot``: one copy per live page
        (mapped and starting at or before the query position) with its
        liveness, and the block's row of gathered positions."""
        pages = []
        for j in range(ppb):             # static: the block's pages
            m = k * ppb + j
            # a page past the table (m >= n_m) starts past every position,
            # so it is dead; the clamp only keeps the table read in bounds
            pid = block_ref[b, jnp.minimum(m, n_m - 1)]
            live = (pid != 0) & (m * page <= pos_ref[b])
            pages.append((live, pltpu.make_async_copy(
                cp_ref.at[layer, pid], c_buf.at[slot, j], sem.at[slot])))
        pos_row = pltpu.make_async_copy(kvp_ref.at[b, k], p_buf.at[slot],
                                        sem.at[slot])
        return pages, pos_row

    def start(b, k, slot):
        pages, pos_row = copies(b, k, slot)
        pos_row.start()
        for live, cp in pages:
            @pl.when(live)
            def _():
                cp.start()

    def wait(b, k, slot):
        """Wait for block k of slot b; returns whether any page is live."""
        pages, pos_row = copies(b, k, slot)
        pos_row.wait()
        any_live = False
        for live, cp in pages:
            @pl.when(live)
            def _():
                cp.wait()
            any_live = any_live | live
        return any_live

    def attend(b, slot):
        q = q_ref[b]                                     # (H, W)
        c = c_buf[slot].astype(q.dtype)                  # (ppb, P, W)
        if kv_scale:                                     # int8 pool
            c = c * kv_scale
        c = c.reshape(bk, c.shape[-1])
        kv_pos = p_buf[slot]                             # (1, bk)
        pos = pos_ref[b]
        valid = (kv_pos >= 0) & (kv_pos <= pos)
        s = jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)                 # (H, bk)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(q.dtype), c[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # a masked entry weighs exactly 0, and so does a buffer row no copy
    # filled: it holds an earlier block's entry or this zero, never NaN
    c_buf[...] = jnp.zeros_like(c_buf)
    start(0, 0, 0)

    def step(_, carry):
        b, k, slot = carry
        last = k + 1 == n_walk(b)
        nb = jnp.where(last, b + 1, b)
        nk = jnp.where(last, 0, k + 1)

        @pl.when(nb < B)                 # the next block, across slots too
        def _():
            start(nb, nk, 1 - slot)

        @pl.when(k == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(wait(b, k, slot))
        def _():
            attend(b, slot)

        @pl.when(last)
        def _():
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[b] = (acc_ref[...] / l).astype(o_ref.dtype)
        return nb, nk, 1 - slot

    n_steps = jax.lax.fori_loop(0, B, lambda b, n: n + n_walk(b), 0)
    jax.lax.fori_loop(0, n_steps, step, (0, 0, 0))


def paged_latent_attention_impl(q, cp, ppos, block, position, layer, *,
                                dv: int, scale: float, kv_scale: float = 0.0,
                                interpret: bool = False):
    """Fused paged decode over a latent (MLA) pool, absorbed form.

    q: (B, H, W) — each head's query already multiplied into the latent
    space (``q_nope @ W_UK``) and followed by its rotary part; cp:
    (L, n_pages, P, W) physical pools of [latent | rotary key] entries,
    stacked over layers, of which ``layer`` (a traced scalar) is read —
    the layer scan hands the kernel the whole stack, so no layer's pool is
    copied for it (int8 when ``kv_scale``); ppos: (L, n_pages, P); block:
    (B, M) the layer's block table; position: (B,). Every head attends
    over the same one entry per position, and the value is that entry's
    first ``dv`` columns, so each page is read once for all heads.
    Returns (B, H, dv) latent outputs in q.dtype (the caller applies
    ``W_UV``).

    One kernel call walks every slot's live blocks in turn. A block is
    ``ppb = 128 // P`` pages (at least one): the kernel copies a block's
    live pages (mapped, starting at or before the query position) from the
    pool, which stays in HBM, into a VMEM buffer, and starts the next
    block's copies — the next slot's first block after a slot's last —
    before it attends over the current one. A slot's walk ends at the
    block holding its query position, so dead blocks cost nothing and
    dead pages of the last block are never fetched. The entries' positions
    come gathered per block table, -1 where a page is unmapped or past the
    query: the kernel masks by them exactly as ``_latent_attend`` masks
    (``block != 0``, ``0 <= ppos <= position``)."""
    B, H, W = q.shape
    P = ppos.shape[-1]
    M = block.shape[1]
    ppb = max(1, 128 // P)
    n_blk = -(-M // ppb)
    block = block.astype(jnp.int32)
    position = position.astype(jnp.int32)
    # a kernel copy moves whole 128-lane tiles and a page's ppos row is 16
    # lanes, so the positions travel gathered, one (1, block) row a block
    start = jnp.arange(M, dtype=jnp.int32) * P
    live = (block != 0) & (start[None] <= position[:, None])
    kv_pos = jnp.where(live[..., None], jnp.take(ppos[layer], block, axis=0),
                       -1)
    kv_pos = jnp.pad(kv_pos.reshape(B, M * P),
                     ((0, 0), (0, n_blk * ppb * P - M * P)),
                     constant_values=-1).reshape(B, n_blk, 1, ppb * P)
    whole = lambda i, *_: (0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[pl.BlockSpec((B, H, W), whole),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((B, H, dv), whole),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, P, W), cp.dtype),
            pltpu.VMEM((2, 1, ppb * P), jnp.int32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dv), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_latent_kernel, page=P, ppb=ppb, n_m=M,
                               dv=dv, kv_scale=kv_scale, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), block, position, q, cp,
      kv_pos)


paged_latent_attention = functools.partial(jax.jit, static_argnames=(
    "dv", "scale", "kv_scale", "interpret"))(paged_latent_attention_impl)


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
