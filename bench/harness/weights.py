"""Weights from the seed, made on the device in one jitted call, in the type
they are served in. The layout (names, shapes, types) comes from the
configuration's plain reference; the harness checks it against the tree the
program expects before serving.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_is_leaf = lambda x: isinstance(x, tuple) and len(x) == 3 and \
    isinstance(x[0], tuple)


def key_for(seed: int):
    """A PRNG key from any whole seed (more than 32 bits kept)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


def _leaf(key, shape, dtype, init, d_model):
    dt = jnp.dtype(dtype)
    if init == "ones":
        return jnp.ones(shape, dt)
    if init == "ssm_a":                       # A_log = log U[1, 16]
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if init == "ssm_dt":                      # softplus^-1(log-uniform dt)
        u = jax.random.uniform(key, shape, jnp.float32)
        dt_ = jnp.exp(u * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3))
        return jnp.log(jnp.expm1(dt_))
    if init == "embed":                       # unit-scale tied logits
        std = d_model ** -0.5
    elif init == "conv":                      # depthwise: fan-in = width
        std = shape[-1] ** -0.5
    else:                                     # fan-in scaled projections
        std = shape[-2] ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dt)


def make(layout: dict, seed: int):
    """Materialise ``layout`` from ``seed`` on the default device."""
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_leaf)
    d_model = layout["embed"][0][1]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            _leaf(k, *leaf, d_model) for k, leaf in zip(keys, leaves)])

    return build(key_for(seed))


def check_against(layout: dict, abstract) -> None:
    """Raise when the program's parameter tree differs from the layout."""
    want = jax.tree.map(lambda l: (tuple(l[0]), jnp.dtype(l[1]).name),
                        layout, is_leaf=_is_leaf)
    got = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype).name),
                       abstract)
    if jax.tree.structure(want, is_leaf=lambda x: isinstance(x, tuple)) != \
            jax.tree.structure(got, is_leaf=lambda x: isinstance(x, tuple)) \
            or want != got:
        raise RuntimeError(f"program parameter tree differs from the "
                           f"benchmark's layout:\nwant {want}\ngot  {got}")
