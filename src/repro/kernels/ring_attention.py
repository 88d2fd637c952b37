"""Ring-attention sequence-parallel chunked prefill.

One ``shard_map`` region wraps the blocked online-softmax flash cell: each
shard keeps its query rows *resident* while the K/V context (plus its
absolute positions) rotates around the ring via ``jax.lax.ppermute``. The
online-softmax state (m, l, acc) is carried across ring hops exactly the way
``kernels.flash_attention`` carries it across KV blocks — the per-hop Pallas
kernel below IS that kernel with the scratch state promoted to pallas-call
operands/outputs so a hop can resume where the previous one stopped.

Masking is *explicit-position* based (absolute ``q_pos`` / ``kv_pos``, -1 =
empty), never iota-derived, which makes correctness layout-invariant: any
permutation of the sequence dims preserves every (q, kv) pair's mask, only
the fp accumulation order changes. That freedom buys the two scheduling
tricks:

* **striped causal layout** — causal chunks assign query rows round-robin
  (row ``i`` -> shard ``i % n``) so every shard sees the same mix of early
  and late positions and the ring stays load-balanced (striped attention);
* **whole-hop skipping** — a hop whose visiting K/V shard is entirely in
  the future of every resident query (causal) or entirely behind the
  attention band (window mode, contiguous layout) is skipped with a
  ``lax.cond`` around the whole pallas call; inside a running hop the same
  position bounds skip individual (q-block, kv-block) tiles.

The per-device cost model at the bottom is what the explorer/roofline price
admission with and what ``benchmarks/kernel_bench.py`` persists: resident
queries and the initial K/V shard split ``n_shards`` ways; rotating tiles
are assumed to stay VMEM-resident between hops (a few MB per hop at 32k),
so the ring moves ICI wire bytes, not HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30
_BIG = 2 ** 30


def _hop_kernel(q_ref, k_ref, v_ref, qp_ref, kvp_ref, mi_ref, li_ref, ai_ref,
                mo_ref, lo_ref, ao_ref, m_s, l_s, a_s, *,
                bq: int, bk: int, n_k: int, window: int, cap: float,
                kv_scale: float, scale: float):
    """One ring hop: flash_attention._kernel with carried (m, l, acc) state
    entering as operands and leaving as outputs, and explicit-position
    masking instead of iota (the layout may be striped and the context may
    contain holes)."""
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_s[...] = mi_ref[0, 0]
        l_s[...] = li_ref[0, 0]
        a_s[...] = ai_ref[0, 0]

    qpos = qp_ref[0].reshape(bq, 1)
    kpos = kvp_ref[0]                            # (1, bk)
    q_ok, kv_ok = qpos >= 0, kpos >= 0
    # tile-level skip from position bounds (striped-attention block skip)
    q_max = jnp.max(jnp.where(q_ok, qpos, -1))
    kv_min = jnp.min(jnp.where(kv_ok, kpos, _BIG))
    run = jnp.any(kv_ok) & jnp.any(q_ok) & (kv_min <= q_max)
    if window:
        q_min = jnp.min(jnp.where(q_ok, qpos, _BIG))
        kv_max = jnp.max(jnp.where(kv_ok, kpos, -1))
        run &= kv_max > q_min - window

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        if kv_scale:
            k = k * kv_scale
            v = v * kv_scale
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if cap:
            s = cap * jnp.tanh(s / cap)
        mask = kv_ok & q_ok & (kpos <= qpos)
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_s[...], l_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # rows still fully masked have m_new == NEG_INF and s - m_new == 0;
        # the mask (not the exp) must zero them or they'd accumulate 1s
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        m_s[...] = m_new
        a_s[...] = (a_s[...] * alpha
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))

    @pl.when(j == n_k - 1)
    def _finish():
        mo_ref[0, 0] = m_s[...]
        lo_ref[0, 0] = l_s[...]
        ao_ref[0, 0] = a_s[...]


def _hop(qf, kf, vf, qp, kvp, m, l, acc, *, window: int, cap: float,
         kv_scale: float, interpret: bool, bq: int = 128, bk: int = 128):
    """Advance the online-softmax state by one hop's K/V tile.

    qf: (B, H, Cl, hd); kf/vf: (B, KVH, Ll, hd) at storage dtype; qp: (B,
    Cl); kvp: (B, Ll); m/l: (B, H, Cl, 1) f32; acc: (B, H, Cl, hd) f32.
    Shapes are pre-padded to block multiples by the caller. Positions
    travel as (B, 1, len) views so their blocks span the unit dim (the
    compiler's tiling rule for the last two block dims)."""
    B, H, Cl, hd = qf.shape
    _, KVH, Ll, _ = kf.shape
    rep = H // KVH
    bq, bk = min(bq, Cl), min(bk, Ll)
    grid = (B, H, Cl // bq, Ll // bk)
    kernel = functools.partial(
        _hop_kernel, bq=bq, bk=bk, n_k=Ll // bk, window=window, cap=cap,
        kv_scale=kv_scale, scale=hd ** -0.5)
    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, hd),
                           lambda b, h, i, j, rep=rep: (b, h // rep, j, 0))
    ml_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0))
    f32 = jnp.float32
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec,
                  pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, 0, i)),
                  pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j)),
                  ml_spec, ml_spec, q_spec],
        out_specs=[ml_spec, ml_spec, q_spec],
        out_shape=[jax.ShapeDtypeStruct(m.shape, f32),
                   jax.ShapeDtypeStruct(l.shape, f32),
                   jax.ShapeDtypeStruct(acc.shape, f32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), f32),
                        pltpu.VMEM((bq, 1), f32),
                        pltpu.VMEM((bq, hd), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, qp[:, None], kvp[:, None], m, l, acc)


def _pad_tail(x, axis: int, to: int, fill):
    pad = -x.shape[axis] % to
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def ring_chunk_attention(q, k, v, q_pos, kv_pos, *, mesh, plan, window: int = 0,
                         cap: float = 0.0, kv_scale: float = 0.0,
                         interpret: bool = False):
    """Sequence-parallel attention of one admission chunk over its context.

    q: (B, C, G, R, hd) resident queries; k/v: (B, L, G, hd) the chunk's
    full visible context (cache + in-chunk entries) at storage dtype (int8
    when ``kv_scale`` > 0 — dequantized per hop inside the kernel); q_pos:
    (B, C) absolute positions; kv_pos: (B, L) absolute positions with -1
    marking empty/unmapped entries. Masking is causal (kv <= q) plus the
    sliding-window band when ``window`` > 0, identical to the unsharded
    ``_sdpa`` admission cells. Returns (B, C, G, R, hd) in q's dtype.

    ``plan`` is a ``dist.sharding.PrefillPlan``; the sequence dims of q and
    k/v split over ``plan.seq_axis`` and K/V tiles rotate ``plan.n_shards -
    1`` times. Runs the Pallas hop kernel (interpret mode off-TPU)."""
    B, C, G, R, hd = q.shape
    L = k.shape[1]
    n, ax = plan.n_shards, plan.seq_axis
    g_ax = (plan.kv_head_axis
            if plan.kv_head_axis and G % mesh.shape[plan.kv_head_axis] == 0
            else None)
    q = _pad_tail(q, 1, n, 0)
    q_pos = _pad_tail(q_pos, 1, n, -1)
    k = _pad_tail(k, 1, n, 0)
    v = _pad_tail(v, 1, n, 0)
    kv_pos = _pad_tail(kv_pos, 1, n, -1)
    Cp = q.shape[1]
    inv = None
    if window == 0 and n > 1:
        # striped causal layout: shard d gets query rows d, d+n, d+2n, ...
        stripe = np.concatenate([np.arange(d, Cp, n) for d in range(n)])
        inv = np.argsort(stripe)
        q, q_pos = q[:, stripe], q_pos[:, stripe]

    def region(q_l, k_l, v_l, qp_l, kvp_l):
        B_, Cl, G_l, R_, hd_ = q_l.shape
        H_l = G_l * R_
        qf = q_l.transpose(0, 2, 3, 1, 4).reshape(B_, H_l, Cl, hd_)
        kf = k_l.transpose(0, 2, 1, 3)
        vf = v_l.transpose(0, 2, 1, 3)
        # pad per-shard lengths to kernel block multiples ONCE; the padded
        # K/V buffers ride the ring (all shards symmetric), padded rows are
        # position -1 (masked) and sliced off after the final hop
        bq, bk = min(128, Cl), min(128, kf.shape[2])
        qf = _pad_tail(qf, 2, bq, 0)
        qp_l = _pad_tail(qp_l, 1, bq, -1)
        kf = _pad_tail(kf, 2, bk, 0)
        vf = _pad_tail(vf, 2, bk, 0)
        kvp_l = _pad_tail(kvp_l, 1, bk, -1)
        Clp = qf.shape[2]
        m = jnp.full((B_, H_l, Clp, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((B_, H_l, Clp, 1), jnp.float32)
        acc = jnp.zeros((B_, H_l, Clp, hd_), jnp.float32)
        qv = qp_l >= 0
        q_max = jnp.max(jnp.where(qv, qp_l, -1))
        q_min = jnp.min(jnp.where(qv, qp_l, _BIG))
        ring = [(i, (i + 1) % n) for i in range(n)]
        for hop in range(n):
            kvv = kvp_l >= 0
            kv_min = jnp.min(jnp.where(kvv, kvp_l, _BIG))
            kv_max = jnp.max(jnp.where(kvv, kvp_l, -1))
            # whole-hop skip: this K/V shard entirely empty / in the future
            # (causal) or entirely behind the window band
            run = jnp.any(kvv) & (kv_min <= q_max)
            if window:
                run &= kv_max > q_min - window

            def _go(ops):
                m_, l_, a_, kf_, vf_, kvp_ = ops
                return _hop(qf, kf_, vf_, qp_l, kvp_, m_, l_, a_,
                            window=window, cap=cap, kv_scale=kv_scale,
                            interpret=interpret)

            m, l, acc = jax.lax.cond(run, _go, lambda ops: ops[:3],
                                     (m, l, acc, kf, vf, kvp_l))
            if hop != n - 1:
                kf = jax.lax.ppermute(kf, ax, ring)
                vf = jax.lax.ppermute(vf, ax, ring)
                kvp_l = jax.lax.ppermute(kvp_l, ax, ring)
        o = (acc / jnp.maximum(l, 1e-30))[:, :, :Cl]
        o = o.reshape(B_, G_l, R_, Cl, hd_).transpose(0, 3, 1, 2, 4)
        return o.astype(q_l.dtype)

    q_spec = P(None, ax, g_ax, None, None)
    kv_spec = P(None, ax, g_ax, None)
    p_spec = P(None, ax)
    # pin the operands REPLICATED before the shard_map boundary: the 0.4.x
    # partitioner miscompiles the reshape/stripe-gather/concat chain feeding
    # this region when it also owns the reshard into the ring layout (wrong
    # values, same hazard as the pre-rope gather in models.attention) —
    # forcing the producers to materialize replicated values leaves shard_map
    # a plain local slice
    rep = jax.sharding.NamedSharding(mesh, P())
    q = jax.lax.with_sharding_constraint(q, rep)
    k = jax.lax.with_sharding_constraint(k, rep)
    v = jax.lax.with_sharding_constraint(v, rep)
    q_pos = jax.lax.with_sharding_constraint(q_pos, rep)
    kv_pos = jax.lax.with_sharding_constraint(kv_pos, rep)
    out = jax.shard_map(
        region, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, p_spec, p_spec),
        out_specs=q_spec, check_vma=False)(q, k, v, q_pos, kv_pos)
    if inv is not None:
        out = out[:, inv]
    return out[:, :C]


# ------------------------------------------------- per-device cost account --

def prefill_attn_flops(chunk_len: int, kv_len: int, n_heads: int,
                       head_dim: int) -> float:
    """Attention FLOPs of one admission chunk: QK^T + PV over the full
    visible context (4 * C * L * H * hd). Masking skips roughly half under
    causality; this is the dense upper bound both paths share, so ratios
    between layouts are exact."""
    return 4.0 * chunk_len * kv_len * n_heads * head_dim


def sharded_prefill_attn_flops(chunk_len: int, kv_len: int, n_heads: int,
                               head_dim: int, *, n_shards: int) -> float:
    """Per-DEVICE ring FLOPs: each shard's resident C/n queries visit the
    whole context across the ring's n hops — 1/n_shards of the total."""
    return prefill_attn_flops(math.ceil(chunk_len / n_shards), kv_len,
                              n_heads, head_dim)


def prefill_hbm_bytes(chunk_len: int, kv_len: int, n_kv_heads: int,
                      head_dim: int, *, n_heads: int, kv_bytes: int = 4,
                      q_bytes: int = 4) -> int:
    """HBM traffic of one chunk's attention: read Q + write O (full heads),
    read K + V once (kv heads), plus the int32 position lanes. Scores never
    touch HBM (online softmax in VMEM)."""
    qo = 2 * chunk_len * n_heads * head_dim * q_bytes
    kv = 2 * kv_len * n_kv_heads * head_dim * kv_bytes
    pos = 4 * (chunk_len + kv_len)
    return qo + kv + pos


def sharded_prefill_hbm_bytes(chunk_len: int, kv_len: int, n_kv_heads: int,
                              head_dim: int, *, n_shards: int, n_heads: int,
                              kv_bytes: int = 4, q_bytes: int = 4) -> int:
    """Per-DEVICE ring HBM bytes: the single-device model applied to one
    shard's resident queries and initial K/V shard. Rotating tiles stay
    VMEM-resident between hops (ICI wire, not HBM), so the whole account
    splits n_shards ways."""
    return prefill_hbm_bytes(math.ceil(chunk_len / n_shards),
                             math.ceil(kv_len / n_shards), n_kv_heads,
                             head_dim, n_heads=n_heads, kv_bytes=kv_bytes,
                             q_bytes=q_bytes)
