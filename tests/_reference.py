"""The model-level token-by-token reference the serving tests compare with.

``greedy_warmup_ref`` feeds the prompt through ``lm.decode_step`` one token
at a time on ring caches, then decodes greedily: no engine, no page pool,
no chunked prefill and no megastep.
"""
import functools

import jax
import jax.numpy as jnp

from repro.approx.knobs import PRECISE
from repro.models import lm


@functools.lru_cache(maxsize=None)
def _decode_step(cfg, knobs):
    return jax.jit(lambda p, t, po, c: lm.decode_step(p, t, po, c, cfg,
                                                      knobs))


def greedy_warmup_ref(cfg, params, prompt, n, max_len=64, knobs=None):
    """The first ``n`` greedy tokens after ``prompt``."""
    kn = knobs or PRECISE
    caches = lm.init_caches(cfg, 1, max_len, dtype=jnp.float32,
                            quantized=kn.kv_quant)
    step = _decode_step(cfg, kn)
    out, cursor, cur, pos = [], 0, prompt[0], 0
    while len(out) < n:
        logits, caches = step(params, jnp.asarray([[cur]]),
                              jnp.asarray([pos]), caches)
        pos += 1
        if cursor + 1 < len(prompt):
            cursor += 1
            cur = prompt[cursor]
            continue
        cur = int(jnp.argmax(logits[0]))
        out.append(cur)
    return out
