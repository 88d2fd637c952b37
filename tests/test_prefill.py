"""Batched prefill-with-cache-fill: the handoff caches must continue decode
exactly as a token-by-token warmup would, for every cache family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api, lm
from repro.serve.prefill import prefill_with_cache


@pytest.mark.parametrize("name", ["phi4-mini-3.8b", "gemma2-27b",
                                  "mamba2-780m", "zamba2-2.7b",
                                  "olmoe-1b-7b"])
def test_prefill_handoff_matches_decode_warmup(name):
    cfg = get_config(name + "-smoke")
    if cfg.moe is not None:
        # expert capacity must not bind: batched routing sees all tokens at
        # once while per-token warmup routes tiny batches — different drop
        # sets are expected behavior under tight capacity (see test_dist)
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S, max_len = 2, 12, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)

    # reference: token-by-token decode warmup
    caches_ref = lm.init_caches(cfg, B, max_len, dtype=jnp.float32)
    step = jax.jit(lambda p, t, pos, c: lm.decode_step(p, t, pos, c, cfg))
    logits_ref = None
    for i in range(S):
        logits_ref, caches_ref = step(params, toks[:, i:i+1],
                                      jnp.full((B,), i, jnp.int32),
                                      caches_ref)

    # batched prefill
    logits_pf, caches_pf = jax.jit(
        lambda p, t: prefill_with_cache(p, t, cfg, max_len))(params, toks)
    np.testing.assert_allclose(np.asarray(logits_pf), np.asarray(logits_ref),
                               rtol=3e-3, atol=3e-3)

    # decode continues identically from both cache sets
    nxt = jnp.argmax(logits_pf, -1)[:, None].astype(jnp.int32)
    pos = jnp.full((B,), S, jnp.int32)
    out_ref, _ = step(params, nxt, pos, caches_ref)
    out_pf, _ = step(params, nxt, pos, caches_pf)
    np.testing.assert_allclose(np.asarray(out_pf), np.asarray(out_ref),
                               rtol=3e-3, atol=3e-3)


def test_prefill_window_ring_layout():
    """Local-attention cache smaller than the prompt: only the last W tokens
    survive, and decode continues correctly through the ring."""
    import dataclasses
    cfg = dataclasses.replace(get_config("gemma2-27b-smoke"), window=8)
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S, max_len = 1, 16, 48
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    caches_ref = lm.init_caches(cfg, B, max_len, dtype=jnp.float32)
    step = jax.jit(lambda p, t, pos, c: lm.decode_step(p, t, pos, c, cfg))
    for i in range(S):
        logits_ref, caches_ref = step(params, toks[:, i:i+1],
                                      jnp.full((B,), i, jnp.int32),
                                      caches_ref)
    logits_pf, caches_pf = prefill_with_cache(params, toks, cfg, max_len)
    np.testing.assert_allclose(np.asarray(logits_pf), np.asarray(logits_ref),
                               rtol=3e-3, atol=3e-3)
    nxt = jnp.argmax(logits_pf, -1)[:, None].astype(jnp.int32)
    o1, _ = step(params, nxt, jnp.full((B,), S, jnp.int32), caches_ref)
    o2, _ = step(params, nxt, jnp.full((B,), S, jnp.int32), caches_pf)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                               rtol=3e-3, atol=3e-3)
