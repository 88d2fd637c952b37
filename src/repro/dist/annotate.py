"""Activation-sharding constraints (GSPMD hints) + launcher-set axis registry.

The launcher (dryrun / tests / train driver) declares which mesh axes carry
the batch dim via ``set_batch_axes``; model code then calls ``constrain_batch``
/ ``constrain_vocab`` at residual-stream and logit boundaries. Outside a
``set_mesh`` context (single-device reference paths) every constraint is a
no-op, so the same model code traces on one device and on a mesh.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

Axes = Union[None, str, Tuple[str, ...]]

BATCH_AXES: Axes = None       # mesh axes sharding the batch dim
FSDP_AXIS: Optional[str] = None   # axis weights' embed dim is FSDP-sharded on
VOCAB_AXIS: str = "model"     # TP axis the vocab/logit dim stays sharded on


def set_batch_axes(axes: Axes, fsdp_axis: Optional[str] = None,
                   vocab_axis: str = "model") -> None:
    """Process-global launch declaration (trace-time, like ``flags.UNROLL``)."""
    global BATCH_AXES, FSDP_AXIS, VOCAB_AXIS
    BATCH_AXES = tuple(axes) if isinstance(axes, list) else axes
    FSDP_AXIS = fsdp_axis
    VOCAB_AXIS = vocab_axis


def _flat(axes: Axes) -> Tuple[str, ...]:
    if axes is None:
        return ()
    return axes if isinstance(axes, tuple) else (axes,)


def _usable(mesh, axes: Axes, dim: int) -> bool:
    names = _flat(axes)
    if not names or not all(a in mesh.shape for a in names):
        return False
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return dim % n == 0


def active_mesh():
    """The (abstract) mesh of the enclosing ``jax.set_mesh`` context, or
    None outside one — the single-device reference paths."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def _constrain(x, spec: P):
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(active_mesh(), spec))


def constrain_batch(x):
    """Keep dim 0 (batch) sharded over the declared batch axes."""
    mesh = active_mesh()
    if mesh is None or not _usable(mesh, BATCH_AXES, x.shape[0]):
        return x
    return _constrain(x, P(BATCH_AXES, *([None] * (x.ndim - 1))))


def constrain_replicated(x):
    """Force a full replication boundary (explicit all-gather).

    Used where the 0.4.x SPMD partitioner miscompiles an op combination on a
    TP-sharded dim — e.g. split+concat over a sharded head_dim (rope) returns
    wrong values; gathering first sidesteps it (serving admission path, where
    the gathered chunk K/V are a few tokens wide). No-op off-mesh.
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    return _constrain(x, P(*([None] * x.ndim)))


def constrain_vocab(x):
    """Keep the trailing (vocab) dim TP-sharded — the chunked cross-entropy
    relies on this so GSPMD never replicates the (B, C, V) logit tile."""
    mesh = active_mesh()
    if mesh is None or not _usable(mesh, VOCAB_AXIS, x.shape[-1]):
        return x
    lead = BATCH_AXES if _usable(mesh, BATCH_AXES, x.shape[0]) else None
    return _constrain(x, P(lead, *([None] * (x.ndim - 2)), VOCAB_AXIS))
