"""Slot-scatter helpers for chunked-prefill admission.

``insert_request`` splices a prefilled single-request cache into the engine's
batched KV/Mamba caches; ``convert_caches`` re-encodes the KV rings when a
variant hot-swap crosses the ``kv_quant`` boundary. Both are pure pytree
functions (jit-friendly; ``slot`` may be traced).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import (KVCache, KV_SCALE, PagedKVCache,
                                    PagedLatentCache,
                                    dequantize_kv, quantize_kv)
from repro.models.mamba2 import MambaCache


def insert_request(batched, single, slot):
    """Scatter a prefilled 1-request cache tree into batch row ``slot``.

    Both trees are in ``lm.init_caches`` layout (leaves stacked over layer
    groups, batch at axis 1). Attention rings are rotated so the request's
    entries occupy exactly the slots a token-by-token warmup ending at the
    engine's current cursor would have filled — subsequent decode writes land
    after them and never clobber a live prompt entry until the ring genuinely
    wraps. The batched cursor (global, shared by all slots) is kept.
    """
    def one(bc, sc):
        if isinstance(bc, KVCache):
            W = bc.k.shape[2]
            shift = (bc.cursor[0] - sc.cursor[0]) % W
            roll = lambda x: jnp.roll(x, shift, axis=2)
            return KVCache(
                k=bc.k.at[:, slot].set(roll(sc.k)[:, 0]),
                v=bc.v.at[:, slot].set(roll(sc.v)[:, 0]),
                pos=bc.pos.at[:, slot].set(roll(sc.pos)[:, 0]),
                cursor=bc.cursor)
        assert isinstance(bc, MambaCache), type(bc)
        return MambaCache(*(b.at[:, slot].set(s[:, 0])
                            for b, s in zip(bc, sc)))

    return tuple(one(b, s) for b, s in zip(batched, single))


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
