"""Wire-compressed collectives — the executable side of the Pliant sync knobs.

* ``compressed_pmean``  — mean over a shard_map axis with an int8-quantized
  wire format: each peer ships (int8 payload, one f32 scale) instead of f32,
  ~4x fewer collective bytes. This is the real implementation of the
  ``grad_compress`` knob.
* ``grad_sync``         — the per-step gradient reduction as ONE owned
  shard_map region: explicit in-pod pmean over ``data`` plus (when the knobs
  call for it) the cross-pod wire in the same region. Because the pod wire is
  either traced into the region or not, ``sync_period`` elision is a
  trace-time fact — the compiled step carries zero pod collective bytes.
* ``pod_sync_params``   — periodic pod-level parameter sync for the
  ``sync_period`` knob (local-SGD style): a train step under
  ``sync_period=k`` carries no cross-pod collectives; the launcher calls this
  every k steps instead (``launch/train.py``), amortizing the wire cost.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def _quantize_int8(x):
    """Symmetric per-tensor int8: (payload int8, scale f32 scalar)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_pmean(tree, axis_name: str):
    """Mean over ``axis_name`` (inside shard_map) with int8 wire payloads.

    Scales differ per peer, so the reduction is an all_gather of the int8
    payloads + scales followed by a local dequantized mean — the wire carries
    int8; only the (scalar-per-peer) scales travel as f32.
    """
    def one(x):
        q, scale = _quantize_int8(x)
        qg = jax.lax.all_gather(q, axis_name)            # int8 on the wire
        sg = jax.lax.all_gather(scale, axis_name)
        deq = qg.astype(jnp.float32) * sg.reshape((-1,) + (1,) * x.ndim)
        return jnp.mean(deq, axis=0).astype(x.dtype)
    return jax.tree.map(one, tree)


def _pspec_of(s):
    return s.spec if isinstance(s, NamedSharding) else s


def _spec_axes(spec):
    """Mesh-axis names a PartitionSpec partitions over (flattened)."""
    names = set()
    for part in spec:
        if part is None:
            continue
        if isinstance(part, (tuple, list)):
            names.update(part)
        else:
            names.add(part)
    return names


def _is_spec(s):
    return isinstance(s, (NamedSharding, P))


def grad_sync(grads, mesh, *, pod_wire: bool = True, compress: bool = False,
              pspecs=None, data_axis: str = "data", pod_axis: str = "pod"):
    """The whole per-step gradient reduction as one shard_map region.

    In-pod: an explicit pmean over ``data_axis`` for every leaf that is not
    itself ``data``-sharded (FSDP leaves already live reduced-and-scattered).
    On grads that GSPMD has already reduced this is numerically the identity,
    but it makes the in-pod collective *owned* — visible in the traced jaxpr,
    priceable by the dry-run, and a seam the knobs can rewrite.

    Cross-pod: when ``pod_wire`` (``sync_period == 1``) the pod mean rides in
    the SAME region, int8-compressed when ``compress``. When False the pod
    collective is never traced: sync elision drops the wire bytes from the
    executable itself, not just from the accounting.
    """
    if mesh is None:
        return grads
    have_data = data_axis in mesh.shape
    have_pod = pod_wire and pod_axis in mesh.shape
    if not (have_data or have_pod):
        return grads
    if pspecs is None:
        specs = jax.tree.map(lambda _: P(), grads)
    else:
        specs = jax.tree.map(_pspec_of, pspecs, is_leaf=_is_spec)
    axis_sets = [_spec_axes(s)
                 for s in jax.tree.leaves(specs, is_leaf=_is_spec)]

    def body(g):
        gl, tdef = jax.tree.flatten(g)
        if have_data:
            gl = [x if data_axis in names else jax.lax.pmean(x, data_axis)
                  for x, names in zip(gl, axis_sets)]
        g = tdef.unflatten(gl)
        if have_pod:
            if compress:
                g = compressed_pmean(g, pod_axis)
            else:
                g = jax.tree.map(lambda x: jax.lax.pmean(x, pod_axis), g)
        return g

    return jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                         out_specs=specs, check_vma=False)(grads)


def pod_sync_params(params, mesh, *, compress: bool = False, pspecs=None,
                    axis: str = "pod"):
    """Average ``params`` across the ``axis`` mesh dimension.

    Jit-able from OUTSIDE shard_map: wraps the reduction in a (fully manual)
    shard_map whose in/out specs come from ``pspecs`` (NamedSharding or
    PartitionSpec tree; default replicated). With per-pod-identical params the
    uncompressed sync is exact; ``compress=True`` routes the payload through
    the int8 wire format (used by the dry-run to price the sync step).
    """
    if mesh is None or axis not in mesh.shape:
        return params
    if pspecs is None:
        specs = jax.tree.map(lambda _: P(), params)
    else:
        specs = jax.tree.map(_pspec_of, pspecs,
                             is_leaf=lambda s: isinstance(s, (NamedSharding,
                                                              P)))

    def body(p):
        if compress:
            return compressed_pmean(p, axis)
        return jax.tree.map(lambda x: jax.lax.pmean(x, axis), p)

    return jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                         out_specs=specs, check_vma=False)(params)
