"""Pallas TPU kernel: W8A8 int8 matmul with per-row / per-column scales.

The Pliant *lower-precision* knob lowered to the MXU: int8 operands halve the
HBM traffic of weight streaming vs bf16 and run on the MXU's int8 path.
Blocked (bm x bk) @ (bk x bn) with an fp32 VMEM accumulator carried across the
K grid dimension; scales applied once on the final K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, xs_ref, w_ref, ws_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 partial products on the MXU
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * xs_ref[...] * ws_ref[...]
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def int8_matmul(x_q, x_scale, w_q, w_scale, *, bm: int = 128, bn: int = 128,
                bk: int = 512, out_dtype=jnp.bfloat16, interpret: bool = False):
    """x_q: (M,K) int8; x_scale: (M,1) f32; w_q: (K,N) int8; w_scale: (1,N).

    A row count above ``bm`` that is not a multiple of it (a ragged prefill
    chunk) is padded with zero rows, which are sliced off the result."""
    M, K = x_q.shape
    _, N = w_q.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert N % bn == 0 and K % bk == 0, (N, K, bn, bk)
    pad = -M % bm
    if pad:
        x_q = jnp.pad(x_q, ((0, pad), (0, 0)))
        x_scale = jnp.pad(x_scale, ((0, pad), (0, 0)))
    n_k = K // bk
    grid = ((M + pad) // bm, N // bn, n_k)
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M + pad, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, x_scale, w_q, w_scale)
    return out[:M] if pad else out
