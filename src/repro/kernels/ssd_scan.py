"""Pallas TPU kernel: Mamba2 chunked SSD (state-space duality) scan.

TPU-native adaptation: instead of the GPU warp-level scan, the sequence is
split into MXU-sized chunks; within a chunk the recurrence is expressed as two
dense matmuls (the "duality"), and the (P x N) running state is carried across
chunks in a VMEM scratch accumulator over a sequential grid dimension.

Grid: (B, H, n_chunks) — chunks innermost/sequential per (batch, head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, dtr_ref, a_ref, b_ref, c_ref, o_ref, state_ref,
            *, q: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)           # (Q, P)
    a = a_ref[h]                                  # scalar A_h (negative), SMEM
    dt = dt_ref[0, 0].astype(jnp.float32)         # (Q, 1)
    la = dt * a                                   # (Q, 1) log decay
    la_row = dtr_ref[0, 0].astype(jnp.float32) * a   # (1, Q), same values
    b = b_ref[0].astype(jnp.float32)              # (Q, N)
    c = c_ref[0].astype(jnp.float32)              # (Q, N)

    # inclusive prefix sums of the log decay as triangular matmuls (the TPU
    # kernel compiler has no cumsum), in both orientations so the pairwise
    # decay matrix needs no transpose
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = (rows >= cols).astype(jnp.float32)
    exact = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general(tri, la, (((1,), (0,)), ((), ())),
                              precision=exact,
                              preferred_element_type=jnp.float32)   # (Q, 1)
    cum_row = jax.lax.dot_general(la_row, tri, (((1,), (1,)), ((), ())),
                                  precision=exact,
                                  preferred_element_type=jnp.float32)  # (1, Q)
    total = jnp.sum(la, axis=0, keepdims=True)    # (1, 1)
    # intra-chunk: (C B^T ∘ decay ∘ causal) @ (dt*x)
    g = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q,Q)
    w = jnp.where(rows >= cols, g * jnp.exp(cum - cum_row), 0.0)
    y = jax.lax.dot_general(w, dt * x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk: y += exp(cum) * (C @ S_enter^T);   S_enter: (P, N)
    y += jnp.exp(cum) * jax.lax.dot_general(
        c, state_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0, 0] = y.astype(o_ref.dtype)
    # state update: S = exp(total) S + (w_i * x)^T @ B, w_i = exp(total-cum)*dt
    wi = jnp.exp(total - cum) * dt                # (Q, 1)
    state_ref[...] = (state_ref[...] * jnp.exp(total)
                      + jax.lax.dot_general(
                          wi * x, b, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, b, c, *, chunk: int = 128, interpret: bool = False):
    """x: (B,S,H,P); dt: (B,S,H); a: (H,); b,c: (B,S,N). Returns (B,S,H,P).

    D-skip (y += D*x) is applied by the caller (cheap elementwise).
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    # layout: head-major so one (b,h) owns a contiguous chunk stream; dt
    # travels as a column and as a row (block last dims must tile or span)
    xh = x.transpose(0, 2, 1, 3)                  # (B,H,S,P)
    dth = dt.transpose(0, 2, 1)                   # (B,H,S)
    out = pl.pallas_call(
        functools.partial(_kernel, q=Q),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda i, h, ci: (i, h, ci, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda i, h, ci: (i, h, ci, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda i, h, ci: (i, h, 0, ci)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Q, N), lambda i, h, ci: (i, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda i, h, ci: (i, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda i, h, ci: (i, h, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(xh, dth[..., None], dth[:, :, None, :], a.astype(jnp.float32), b, c)
    return out.transpose(0, 2, 1, 3)
