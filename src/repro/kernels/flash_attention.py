"""Pallas TPU kernel: blocked online-softmax (flash) attention.

Supports causal masking, sliding windows, gemma-style logit softcap, GQA via
index-mapped KV heads, and the Pliant *KV-block perforation* knob: with
``kv_keep_stride = p`` > 1 the kernel skips off-diagonal KV blocks unless
``(i - j) % p == 0``, cutting attention FLOPs and KV HBM traffic — the TPU
lowering of the paper's loop perforation applied to the attention loop.

Grid: (batch, q_heads, q_blocks, kv_blocks); kv innermost (sequential) with
running max / sum-exp / output accumulator in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, n_k: int, causal: bool, window: int,
            cap: float, stride: int, scale: float, n_kv: int):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # static-ish block skip condition evaluated on traced program ids:
    # diagonal + previous block always run; older blocks run at `stride`.
    run = jnp.bool_(True)
    if causal:
        run &= j * bk < (i + 1) * bq
    if window:
        run &= (i * bq - (j + 1) * bk) < window
    if stride > 1:
        near = (i * bq - j * bk) <= 2 * bq
        run &= near | ((i - (j * bk) // bq) % stride == 0)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if cap:
            s = cap * jnp.tanh(s / cap)
        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        if n_k * bk > n_kv:          # padded ragged KV tail: mask it out
            mask &= k_pos < n_kv
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            p.astype(v_ref.dtype), v_ref[0, 0],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    @pl.when(j == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "cap", "kv_keep_stride", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    cap: float = 0.0, kv_keep_stride: int = 1,
                    bq: int = 128, bk: int = 128, interpret: bool = False):
    """q: (B,H,Sq,hd); k/v: (B,KVH,Skv,hd); returns (B,H,Sq,hd).

    Ragged sequence lengths (``Sq``/``Skv`` not multiples of the block size)
    are padded up to the block grid and masked: padded KV columns are
    excluded from every softmax row (explicitly for the tail block, by
    causality for the rest) and padded query rows are sliced off the output
    — no silent miscompute on the final partial block."""
    B, H, Sq, hd = q.shape
    _, KVH, Skv, _ = k.shape
    rep = H // KVH
    bq, bk = min(bq, Sq), min(bk, Skv)
    pad_q, pad_k = -Sq % bq, -Skv % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sqp, Skvp = Sq + pad_q, Skv + pad_k
    grid = (B, H, Sqp // bq, Skvp // bk)
    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, n_k=Skvp // bk, causal=causal, window=window,
        cap=cap, stride=kv_keep_stride, scale=hd ** -0.5, n_kv=Skv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq] if pad_q else out
