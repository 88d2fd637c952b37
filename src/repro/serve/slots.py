"""Cache re-encoding across a variant hot-swap.

``convert_caches`` re-encodes the KV pages (or rings) when a variant
hot-swap crosses the ``kv_quant`` boundary: a pure pytree function.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import (KVCache, KV_SCALE, PagedKVCache,
                                    PagedLatentCache,
                                    dequantize_kv, quantize_kv)


def convert_caches(caches, kv_quant: bool, dtype=jnp.float32):
    """Re-encode KV rings across a ``kv_quant`` hot-swap boundary.

    int8 -> ``dtype`` when leaving a quantized variant, ``dtype`` -> int8 when
    entering one (shared static ``KV_SCALE``, the same rounding decode and
    chunked prefill apply). Positions, cursors, block tables, and Mamba state
    carry over — decode continues mid-request across the swap. Paged pools
    convert every physical page in place (shared prefix pages included, so
    all sharers stay consistent); the engine flushes the knob-tagged prefix
    index on a swap since re-encoded pages match no registered tag.

    The conversion is elementwise per physical page, so under a slot-affinity
    sharded pool (DESIGN.md §13) it is layout-preserving: GSPMD keeps every
    page on its owning device and a hot-swap never migrates pages across
    shards — no re-planning needed around a variant switch.
    """
    q = quantize_kv
    dq = lambda x: dequantize_kv(x, dtype)

    def one(c):
        if isinstance(c, KVCache):
            if kv_quant and c.k.dtype != jnp.int8:
                return c._replace(k=q(c.k), v=q(c.v))
            if not kv_quant and c.k.dtype == jnp.int8:
                return c._replace(k=dq(c.k), v=dq(c.v))
            return c
        if isinstance(c, PagedKVCache):
            if kv_quant and c.kp.dtype != jnp.int8:
                return c._replace(kp=q(c.kp), vp=q(c.vp))
            if not kv_quant and c.kp.dtype == jnp.int8:
                return c._replace(kp=dq(c.kp), vp=dq(c.vp))
            return c
        if isinstance(c, PagedLatentCache):
            if kv_quant != (c.cp.dtype == jnp.int8):
                return c._replace(cp=q(c.cp) if kv_quant else dq(c.cp))
            return c
        return c

    return tuple(one(c) for c in caches)
