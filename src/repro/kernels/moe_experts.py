"""Pallas TPU kernel: the held experts' SwiGLU FFN as one grouped call.

The rows (token picks) arrive sorted by expert and padded per expert to
whole row tiles, so every tile of ``tm`` rows belongs to one expert. The
weights stay stacked over layers and the layer is a scalar argument. The
grid runs (tile, f-block) with the f-block innermost: a step streams one
``tf``-wide block of the tile's expert's gate, up and down matrices
HBM -> VMEM (the expert is scalar-prefetched, so its BlockSpec index maps
straight to its weights) and folds ``silu(x Wg) * (x Wu) Wo`` over the
block into an f32 accumulator. A tile with no rows skips the MXU work and
maps the weights of the tile before it, so its steps fetch nothing; an
expert that no row picked is never read. Weights are stored (experts, F, D)
so that every fetched block is contiguous in HBM.

The int8 rung runs the same grid over int8 weights and their output
channels' scales: each step quantizes its rows and its SwiGLU product per
row and multiplies int8 x int8 into int32 on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))        # x @ w.T
_NN = (((1,), (0,)), ((), ()))        # h @ w


def _q8(a):
    """Symmetric int8 values of ``a`` with one f32 scale per row."""
    amax = jnp.max(jnp.abs(a), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    return jnp.clip(jnp.round(a / scale), -127, 127).astype(jnp.int8), scale


def _kernel(layer_ref, tile_e_ref, tile_n_ref, x_ref, wg_ref, wu_ref, wo_ref,
            *rest, n_f: int, quant: bool):
    if quant:
        gs_ref, us_ref, os_ref, o_ref, acc_ref = rest
    else:
        o_ref, acc_ref = rest
    i, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tile_n_ref[i] > 0)
    def _body():
        x = x_ref[...]
        if quant:
            # W8A8: int8 x int8 -> int32 on the MXU; rows quantized per row,
            # h per row of this f-block
            xq, xs = _q8(x.astype(jnp.float32))
            g = jax.lax.dot_general(xq, wg_ref[0, 0], _NT,
                                    preferred_element_type=jnp.int32)
            u = jax.lax.dot_general(xq, wu_ref[0, 0], _NT,
                                    preferred_element_type=jnp.int32)
            g = g.astype(jnp.float32) * xs * gs_ref[0, 0]
            u = u.astype(jnp.float32) * xs * us_ref[0, 0]
            hq, hs = _q8(g / (1.0 + jnp.exp(-g)) * u)
            acc_ref[...] += jax.lax.dot_general(
                hq, wo_ref[0, 0], _NN,
                preferred_element_type=jnp.int32).astype(jnp.float32) * hs
            return
        g = jax.lax.dot_general(x, wg_ref[0, 0], _NT,
                                preferred_element_type=jnp.float32)
        u = jax.lax.dot_general(x, wu_ref[0, 0], _NT,
                                preferred_element_type=jnp.float32)
        h = (g / (1.0 + jnp.exp(-g)) * u).astype(x.dtype)
        acc_ref[...] += jax.lax.dot_general(
            h, wo_ref[0, 0], _NN, preferred_element_type=jnp.float32)

    @pl.when(f == n_f - 1)
    def _out():
        y = acc_ref[...]
        if quant:
            y = y * os_ref[0, 0]
        o_ref[...] = y.astype(o_ref.dtype)


def block_f(f: int) -> int:
    """The f-block width: 128 lanes where F splits into them, else F."""
    return 128 if f % 128 == 0 else f


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_experts(x, wg, wu, wo, tile_e, tile_n, layer, scales=None, *,
                tm: int, interpret: bool = False):
    """x: (n_tiles * tm, D) rows sorted by expert; wg/wu/wo: (L, E, F, D)
    stacked over layers, of which ``layer`` (a scalar, traced) is used — a
    layer scan hands the kernel the whole stack, so no per-layer slice is
    copied for it; tile_e: (n_tiles,) int32 expert of each tile (a tile
    with no rows names the expert of the tile before it); tile_n:
    (n_tiles,) int32 rows the tile holds (0 = skip). Returns
    (n_tiles * tm, D) in x.dtype; rows of an empty tile are zero, pad rows
    of a partial tile are computed.

    ``scales`` (the int8 rung): the weights are int8 and ``scales`` is
    (gate (L, E, 1, F), up (L, E, 1, F), down (L, E, 1, D)) f32, the scales
    of their output channels; rows are quantized per row and the SwiGLU's
    product per row of each f-block, and every product runs int8 x int8."""
    R, D = x.shape
    F = wg.shape[2]
    tf = block_f(F)
    n_t, n_f = R // tm, F // tf

    def w_map(i, f, l_ref, e_ref, n_ref):
        return (l_ref[0], e_ref[i], jnp.where(n_ref[i] > 0, f, n_f - 1), 0)

    def row_map(i, f, *_):
        return (i, 0)

    def s_map(i, f, l_ref, e_ref, n_ref):
        return (l_ref[0], e_ref[i], 0, jnp.where(n_ref[i] > 0, f, n_f - 1))

    def os_map(i, f, l_ref, e_ref, n_ref):
        return (l_ref[0], e_ref[i], 0, 0)

    quant = scales is not None
    s_specs = [pl.BlockSpec((1, 1, 1, tf), s_map),
               pl.BlockSpec((1, 1, 1, tf), s_map),
               pl.BlockSpec((1, 1, 1, D), os_map)] if quant else []

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_t, n_f),
        in_specs=[pl.BlockSpec((tm, D), row_map),
                  pl.BlockSpec((1, 1, tf, D), w_map),
                  pl.BlockSpec((1, 1, tf, D), w_map),
                  pl.BlockSpec((1, 1, tf, D), w_map)] + s_specs,
        out_specs=pl.BlockSpec((tm, D), row_map),
        scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, n_f=n_f, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_experts",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tile_e.astype(jnp.int32),
      tile_n.astype(jnp.int32), x, wg, wu, wo, *(scales or ()))

