"""Fused paged-attention decode kernel: interpret-mode parity against the
``_gather_pages`` reference path across arch families (full attention, GQA +
softcap + sliding window, hybrid shared-attention dims), ragged per-slot page
counts, partial last pages, and int8 KV — plus the ``active`` write-mask
contract the stall-free serving loop depends on, and the stacked pool the
layer scan carries: every path reads and writes only its own layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn_mod
from repro.models.attention import KV_SCALE, PagedKVCache, quantize_kv
from repro.models.common import init_params

P = 4          # page size
M = 6          # block-table width (max logical pages)


def _setup(name, lengths, *, quantized=False, seed=0, n_layers=1,
           dtype=jnp.float32):
    """Random paged decode state: per-slot prompts of ``lengths`` tokens
    already resident (ragged page counts, partial last pages), the decode
    token landing at position ``lengths[b]``. The pool is stacked over
    ``n_layers`` layers of distinct contents, as the layer scan carries it,
    with the same positions and block tables in each. Returns (cfg,
    params, x, position, cache)."""
    cfg = get_config(name + "-smoke")
    B = len(lengths)
    n_pages = 1 + B * M
    rng = np.random.default_rng(seed)
    hd, G = cfg.resolved_head_dim, cfg.n_kv_heads
    shape = (n_layers, n_pages, P, G, hd)
    if quantized:
        kp = quantize_kv(jnp.asarray(rng.normal(size=shape) * 0.3,
                                     jnp.float32))
        vp = quantize_kv(jnp.asarray(rng.normal(size=shape), jnp.float32))
    else:
        kp = jnp.asarray(rng.normal(size=shape) * 0.3, dtype)
        vp = jnp.asarray(rng.normal(size=shape), dtype)
    block = np.zeros((B, M), np.int32)
    ppos = np.full((n_pages, P), -1, np.int32)
    pid = 1
    for b, L in enumerate(lengths):
        for lp in range(-(-(L + 1) // P)):        # decode writes at pos L
            block[b, lp] = pid
            top = min(L, (lp + 1) * P)            # partial last page
            ppos[pid, : max(top - lp * P, 0)] = np.arange(lp * P, top)
            pid += 1
    params = init_params(attn_mod.attn_specs(cfg), jax.random.PRNGKey(1),
                         jnp.float32)
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)) * 0.3, jnp.float32)
    position = jnp.asarray(np.asarray(lengths, np.int32))
    stack = lambda a: jnp.asarray(np.broadcast_to(a, (n_layers,) + a.shape))
    cache = PagedKVCache(kp, vp, stack(ppos), stack(block))
    return cfg, params, x, position, cache


@pytest.mark.parametrize("name", ["phi4-mini-3.8b",   # full attention (MHA)
                                  "gemma2-27b",       # GQA + softcap + local
                                  "zamba2-2.7b"])     # hybrid shared-attn dims
@pytest.mark.parametrize("window", [0, 8])
@pytest.mark.parametrize("quantized", [False, True])
def test_fused_matches_gather_reference(name, window, quantized):
    lengths = [0, 5, 9, 14]                 # ragged: 1..4 pages, partial tails
    cfg, params, x, position, cache = _setup(name, lengths,
                                             quantized=quantized)
    kv_scale = KV_SCALE if quantized else 0.0
    o_ref, c_ref = attn_mod.paged_decode_attention(
        params, x, position, cache, cfg, layer=0, window=window,
        kv_scale=kv_scale, use_kernel=False)
    o_k, c_k = attn_mod.paged_decode_attention(
        params, x, position, cache, cfg, layer=0, window=window,
        kv_scale=kv_scale, use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    # the scatter side is shared code: caches must match EXACTLY
    for a, b in zip(c_ref, c_k):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_window_skips_out_of_band_pages():
    """With a window, the kernel must ignore pages wholly below the band —
    scrambling their contents must not change the output (the index map
    redirects them to the null page; the body guard skips them)."""
    cfg, params, x, position, cache = _setup("gemma2-27b", [15, 18])
    window = 6
    o1, _ = attn_mod.paged_decode_attention(
        params, x, position, cache, cfg, layer=0, window=window,
        use_kernel=True, interpret=True)
    # pages 0..1 of each slot hold positions <= 11 <= min(pos) - window
    dead = np.asarray(cache.block[0, :, :2]).ravel()
    kp = cache.kp.at[:, jnp.asarray(dead)].set(1e3)
    vp = cache.vp.at[:, jnp.asarray(dead)].set(-1e3)
    o2, _ = attn_mod.paged_decode_attention(
        params, x, position, cache._replace(kp=kp, vp=vp), cfg, layer=0,
        window=window, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_active_mask_blocks_inactive_writes(use_kernel):
    """The stall-free loop's contract: rows with ``active=False`` (slots
    mid-admission or empty) must not scatter their garbage token into the
    pool — pages AND ppos stay bit-identical for inactive rows."""
    cfg, params, x, position, cache = _setup("phi4-mini-3.8b", [6, 9])
    active = jnp.asarray(np.array([True, False]))
    _, c_new = attn_mod.paged_decode_attention(
        params, x, position, cache, cfg, layer=0, active=active,
        use_kernel=use_kernel, interpret=use_kernel)
    # row 1's tail page (the write target) must be untouched
    tail1 = int(cache.block[0, 1, 9 // P])
    np.testing.assert_array_equal(np.asarray(c_new.kp[0, tail1]),
                                  np.asarray(cache.kp[0, tail1]))
    np.testing.assert_array_equal(np.asarray(c_new.ppos[0, tail1]),
                                  np.asarray(cache.ppos[0, tail1]))
    # row 0's write DID land
    tail0 = int(cache.block[0, 0, 6 // P])
    assert int(c_new.ppos[0, tail0, 6 % P]) == 6


@pytest.mark.parametrize("use_kernel", [False, True])
def test_speculative_future_pages_do_not_change_output(use_kernel):
    """Grouped admission maps a request's projected decode pages up front
    (scrubbed: ``ppos`` = -1). Decode output must be bit-identical whether
    or not those future pages are mapped, regardless of their K/V contents
    — the kernel's index map redirects wholly-future pages to the null
    page; the gather path masks their empty ``ppos`` rows."""
    lengths = [5, 9]
    cfg, params, x, position, cache = _setup("phi4-mini-3.8b", lengths)
    o1, _ = attn_mod.paged_decode_attention(
        params, x, position, cache, cfg, layer=0, use_kernel=use_kernel,
        interpret=use_kernel)
    block = np.asarray(cache.block[0]).copy()
    used = {int(p) for p in block.ravel()}
    fresh = [p for p in range(1, int(cache.kp.shape[1])) if p not in used]
    scramble = []
    for b, L in enumerate(lengths):
        for m in range(-(-(L + 1) // P), M):    # wholly past the query pos
            block[b, m] = scramble_pid = fresh.pop()
            scramble.append(scramble_pid)
    kp = cache.kp.at[:, jnp.asarray(scramble)].set(1e3)
    vp = cache.vp.at[:, jnp.asarray(scramble)].set(-1e3)
    c2 = cache._replace(kp=kp, vp=vp, block=jnp.asarray(block[None]))
    o2, _ = attn_mod.paged_decode_attention(
        params, x, position, c2, cfg, layer=0, use_kernel=use_kernel,
        interpret=use_kernel)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


@pytest.mark.parametrize("active_mask", [None, (True, False)])
def test_dyn_scatter_write_matches_one_hot(active_mask):
    """The dynamic-index cache write (single-device engines) must land the
    decode token bit-identically to the one-hot masked scatter on every
    LIVE page; inactive rows write only the never-read null page."""
    cfg, params, x, position, cache = _setup("phi4-mini-3.8b", [6, 9])
    active = (None if active_mask is None
              else jnp.asarray(np.asarray(active_mask)))
    o1, c1 = attn_mod.paged_decode_attention(
        params, x, position, cache, cfg, layer=0, active=active,
        use_kernel=False)
    o2, c2 = attn_mod.paged_decode_attention(
        params, x, position, cache, cfg, layer=0, active=active,
        use_kernel=False, dyn_scatter=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(c1.block), np.asarray(c2.block))
    # page 0 is the trash page: dyn-scatter parks masked rows there,
    # one-hot never touches it — both are fine, nothing ever reads it
    for a, b in ((c1.kp, c2.kp), (c1.vp, c2.vp), (c1.ppos, c2.ppos)):
        np.testing.assert_array_equal(np.asarray(a[:, 1:]),
                                      np.asarray(b[:, 1:]))


@pytest.mark.parametrize("variant", ["bf16", "int8", "window"])
def test_stacked_pool_reads_and_writes_its_layer(variant):
    """The layer scan hands every paged call the whole stacked pool and the
    layer's index. At layer 1 of a 3-layer stack, the fused kernel must
    match the gather path, and both must equal the same call on that layer
    alone; a decode step and an admission chunk (both write forms) must
    leave every other layer's K, V and positions bit-identical."""
    lengths = [0, 5, 9, 14]
    quantized = variant == "int8"
    window = 8 if variant == "window" else 0
    kv_scale = KV_SCALE if quantized else 0.0
    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32
    cfg, params, x, position, cache = _setup(
        "gemma2-27b", lengths, quantized=quantized, n_layers=3, dtype=dtype)
    layer = jnp.asarray(1, jnp.int32)
    alone = PagedKVCache(*(a[1:2] for a in cache))
    kw = dict(window=window, kv_scale=kv_scale)

    o_ref, c_ref = attn_mod.paged_decode_attention(
        params, x, position, cache, cfg, layer=layer, use_kernel=False, **kw)
    o_k, c_k = attn_mod.paged_decode_attention(
        params, x, position, cache, cfg, layer=layer, use_kernel=True,
        interpret=True, **kw)
    o_1, c_1 = attn_mod.paged_decode_attention(
        params, x, position, alone, cfg, layer=0, use_kernel=False, **kw)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(o_ref), np.asarray(o_1))
    for new, ref, one, old in zip(c_k, c_ref, c_1, cache):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(one[0]))
        for other in (0, 2):
            np.testing.assert_array_equal(np.asarray(new[other]),
                                          np.asarray(old[other]))

    # one admission chunk of slot 3, over positions it has mapped
    slot = jnp.asarray(3, jnp.int32)
    pos_c = jnp.arange(10, 14, dtype=jnp.int32)[None]
    xc = jnp.asarray(np.random.default_rng(3).normal(
        size=(1, 4, cfg.d_model)) * 0.3, jnp.float32)
    for dyn in (False, True):
        y, c_new = attn_mod.paged_chunk_attention(
            params, xc, pos_c, cache, cfg, slot, layer=layer,
            dyn_scatter=dyn, **kw)
        y1, c_one = attn_mod.paged_chunk_attention(
            params, xc, pos_c, alone, cfg, slot, layer=0, dyn_scatter=dyn,
            **kw)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y1))
        for new, one, old in zip(c_new, c_one, cache):
            np.testing.assert_array_equal(np.asarray(new[1]),
                                          np.asarray(one[0]))
            for other in (0, 2):
                np.testing.assert_array_equal(np.asarray(new[other]),
                                              np.asarray(old[other]))
        assert not np.array_equal(np.asarray(c_new.kp[1]),
                                  np.asarray(cache.kp[1]))


def test_mamba_decode_active_mask_preserves_state():
    from repro.models import mamba2
    cfg = get_config("mamba2-780m-smoke")
    params = init_params(mamba2.mamba_specs(cfg), jax.random.PRNGKey(0),
                         jnp.float32)
    cache = mamba2.init_mamba_cache(cfg, 2, jnp.float32)
    cache = mamba2.MambaCache(*(x + 0.5 for x in cache))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 1, cfg.d_model)),
                    jnp.float32)
    _, c_new = mamba2.mamba_decode(params, x, cache, cfg,
                                   active=jnp.asarray([False, True]))
    for old, new in zip(cache, c_new):
        np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(old[0]))
        assert not np.array_equal(np.asarray(new[1]), np.asarray(old[1]))
