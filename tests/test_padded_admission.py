"""Padded paged admission: a ragged prompt tail run through the full-chunk
admission program, its pad rows after the valid ones, gives what the
exact-length program gives; attention-only and (dropless) expert paged
engines obtain one admission program per knob set, while SSM
configurations keep one per tail length and pad nothing."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.approx.knobs import PRECISE, ApproxKnobs
from repro.configs import get_config
from repro.models import api, lm
from repro.models.attention import PagedKVCache
from repro.serve.engine import Request, ServeEngine
from repro.serve.prefill import PAD_TOKEN, pads_exactly, prefill_with_cache
from repro.train import step as step_mod

CHUNK, PAGE, N_PAGES, MAX_PAGES, SLOT = 8, 4, 16, 8, 1


@functools.lru_cache(maxsize=None)
def model(name, window=0):
    cfg = get_config(name + "-smoke")
    if window:
        cfg = dataclasses.replace(cfg, window=window)
    return cfg, api.init(cfg, jax.random.PRNGKey(0), jnp.float32)


def mapped_caches(cfg, kv_quant, pages):
    """Fresh paged caches with ``pages`` mapped on SLOT's block row."""
    caches = lm.init_paged_caches(cfg, 2, N_PAGES, PAGE, MAX_PAGES,
                                  dtype=jnp.float32, quantized=kv_quant)
    row = jnp.zeros(MAX_PAGES, jnp.int32).at[:len(pages)].set(
        jnp.asarray(pages, jnp.int32))
    return tuple(c._replace(block=c.block.at[:, SLOT].set(row))
                 for c in caches)


@pytest.mark.parametrize("dyn_scatter", [True, False])
@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("name,window", [("phi4-mini-3.8b", 0),
                                         ("gemma2-27b", 8)])   # local+global
def test_padded_tail_equals_exact_tail(name, window, kv_quant, dyn_scatter):
    cfg, params = model(name, window)
    knobs = ApproxKnobs(kv_quant=True) if kv_quant else PRECISE
    fn = jax.jit(step_mod.make_paged_admission_step(
        cfg, knobs, dynamic_scatter=dyn_scatter))
    # 19 prompt tokens over pages 3, 5, 7, 9, 11; page 13 mapped for decode
    pages = [3, 5, 7, 9, 11, 13]
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab_size, 2 * CHUNK + 3).astype(np.int32)
    slot = jnp.asarray(SLOT, jnp.int32)
    caches = mapped_caches(cfg, kv_quant, pages)
    for s in (0, CHUNK):                          # two full chunks first
        _, _, caches = fn(params, jnp.asarray(prompt[None, s:s + CHUNK]),
                       jnp.asarray(s, jnp.int32), caches, slot)
    before = jax.tree.map(np.asarray, caches)
    start = jnp.asarray(2 * CHUNK, jnp.int32)
    tail = prompt[2 * CHUNK:]
    exact_logits, _, exact = fn(params, jnp.asarray(tail[None]), start,
                             jax.tree.map(jnp.asarray, before), slot)
    padded_toks = np.full(CHUNK, PAD_TOKEN, np.int32)
    padded_toks[:len(tail)] = tail
    pad_logits, _, padded = fn(params, jnp.asarray(padded_toks[None]),
                               start,
                            jax.tree.map(jnp.asarray, before), slot)

    np.testing.assert_allclose(np.asarray(pad_logits),
                               np.asarray(exact_logits),
                               rtol=1e-5, atol=1e-5)
    end = len(prompt)
    for c_ex, c_pad, c_0 in zip(exact, padded, before):
        assert isinstance(c_pad, PagedKVCache)
        np.testing.assert_array_equal(np.asarray(c_pad.block), c_0.block)
        for leaf in ("kp", "vp", "ppos"):
            ex, pd, b0 = (np.asarray(getattr(c, leaf))
                          for c in (c_ex, c_pad, c_0))
            assert np.isfinite(pd.astype(np.float32)).all()
            # every page but the null page as the exact program left it
            np.testing.assert_allclose(pd[:, 1:].astype(np.float32),
                                       ex[:, 1:].astype(np.float32),
                                       rtol=1e-6, atol=1e-6, err_msg=leaf)
            # the prompt's entries landed; the rest of the pool is untouched
            for i, pid in enumerate(pages):
                for off in range(PAGE):
                    if i * PAGE + off < end:
                        continue
                    np.testing.assert_array_equal(pd[:, pid, off],
                                                  b0[:, pid, off])
            rest = [p for p in range(1, N_PAGES) if p not in pages]
            np.testing.assert_array_equal(pd[:, rest], b0[:, rest])
        ppos = np.asarray(c_pad.ppos)
        got = [ppos[:, pages[q // PAGE], q % PAGE] for q in range(end)]
        assert all((g == q).all() for q, g in enumerate(got))


@pytest.mark.parametrize("length", [3, CHUNK])
def test_all_valid_block_is_the_plain_computation(length):
    """An all-valid block of any length computes what a one-shot prefill
    does: blocks of every length prime the admission program's cache."""
    cfg, params = model("phi4-mini-3.8b")
    fn = jax.jit(step_mod.make_paged_admission_step(cfg, PRECISE))
    toks = np.random.default_rng(2).integers(
        1, cfg.vocab_size, (1, length)).astype(np.int32)
    caches = mapped_caches(cfg, False, [2, 4, 6])
    logits, _, _ = fn(params, jnp.asarray(toks), jnp.asarray(0, jnp.int32),
                   caches, jnp.asarray(SLOT, jnp.int32))
    # the reference: the last row of a one-shot prefill of the same tokens
    ref, _ = prefill_with_cache(params, jnp.asarray(toks), cfg, 32)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def serve(name, lengths, pad=True, max_new=4):
    cfg, params = model(name)
    eng = ServeEngine(cfg, batch_slots=2, max_len=64, params=params,
                      prefill_chunk=4, page_size=4)
    if not pad:
        eng._pad_chunks = False           # the exact-length path
    asked = []
    orig = eng._prefill_exe

    def counting(c):
        asked.append(c)
        return orig(c)

    eng._prefill_exe = counting
    rng = np.random.default_rng(9)
    reqs = [Request(i, prompt=list(map(int, rng.integers(
        1, cfg.vocab_size, n))), max_new=max_new)
        for i, n in enumerate(lengths)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    return eng, [r.out for r in reqs], asked


LENGTHS = (5, 6, 7, 9, 12)                # tails 1, 2, 3, 1 and none


def test_attention_only_engine_obtains_one_admission_program():
    assert pads_exactly(model("phi4-mini-3.8b")[0])
    eng, outs, asked = serve("phi4-mini-3.8b", LENGTHS)
    assert sorted(set(asked)) == [1, 2, 3, 4]
    assert eng.stats["prefill_exe_misses"] == 1
    assert len(eng._prefills) == 1
    assert eng.stats["prefill_pad_tokens"] == 3 + 2 + 1 + 3
    exact, ref_outs, _ = serve("phi4-mini-3.8b", LENGTHS, pad=False)
    assert exact.stats["prefill_exe_misses"] == 4
    assert exact.stats["prefill_pad_tokens"] == 0
    assert outs == ref_outs


@pytest.mark.parametrize("name", ["zamba2-2.7b",      # SSM hybrid
                                  "olmoe-1b-7b"])     # experts
def test_ssm_and_expert_engines_keep_exact_lengths(name):
    """SSM engines keep one admission program per tail length. Expert
    engines kept one too while their layer dropped picks past a capacity;
    the dropless layer gives pad rows no expert rows, so they now pad like
    attention-only engines, obtain one program, and serve what the
    exact-length path serves."""
    cfg = model(name)[0]
    eng, outs, asked = serve(name, LENGTHS)
    assert len(set(asked)) > 1
    if cfg.moe is None:
        assert not pads_exactly(cfg)
        assert eng.stats["prefill_exe_misses"] == len(set(asked))
        assert eng.stats["prefill_pad_tokens"] == 0
        return
    assert pads_exactly(cfg)
    assert eng.stats["prefill_exe_misses"] == 1
    assert eng.stats["prefill_pad_tokens"] == 3 + 2 + 1 + 3
    exact, ref_outs, _ = serve(name, LENGTHS, pad=False)
    assert exact.stats["prefill_exe_misses"] == len(set(asked))
    assert outs == ref_outs
