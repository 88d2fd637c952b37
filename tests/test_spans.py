"""Engine spans and program names: the span log records nothing while off;
on, the paged engine's layer spans nest inside ``engine.step``, carry the
request they served, and mark each program the engine obtains; the step
programs and the paged-attention kernel lower under their own names."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.serve.engine import Request, ServeEngine
from repro.serve.spans import SpanLog

LAYERS = {"engine.step", "sched.admit", "prefill.chunk", "decode.dispatch",
          "pool.push_blocks", "decode.drain", "admit.drain",
          "program.obtain"}


@functools.lru_cache(maxsize=None)
def model():
    cfg = get_config("phi4-mini-3.8b-smoke")
    return cfg, api.init(cfg, jax.random.PRNGKey(0), jnp.float32)


def engine(megastep_k=4):
    cfg, params = model()
    return ServeEngine(cfg, batch_slots=2, max_len=64, params=params,
                       prefill_chunk=4, paged=True, page_size=4,
                       megastep_k=megastep_k)


def serve(eng, n=3):
    """Five-to-eleven-token prompts: full chunks and ragged tails."""
    rng = np.random.default_rng(7)
    reqs = [Request(i, prompt=list(map(int, rng.integers(
        1, eng.cfg.vocab_size, 5 + 3 * i))), max_new=5) for i in range(n)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    return reqs


@functools.lru_cache(maxsize=None)
def traced(megastep_k):
    eng = engine(megastep_k)
    eng.spans.enable()
    reqs = serve(eng)
    return eng, reqs, eng.spans.take()


def test_spans_off_record_nothing():
    eng = engine()
    serve(eng)
    assert not eng.spans.on
    assert eng.spans.take() == []
    assert eng.stats["programs_obtained"] > 0     # counters count regardless


@pytest.mark.parametrize("megastep_k", [0, 4])
def test_spans_nest_inside_their_parents(megastep_k):
    eng, _, spans = traced(megastep_k)
    by_id = {s.sid: s for s in spans}
    assert {s.name for s in spans} == LAYERS
    for s in spans:
        assert s.t0 <= s.t1
        if s.name == "engine.step":
            assert s.parent == 0
            continue
        p = by_id[s.parent]
        assert p.t0 <= s.t0 and s.t1 <= p.t1, (s, p)
    roots = [s for s in spans if s.name == "engine.step"]
    assert len(roots) == eng.step_count
    # one decode token per drained row per step on the per-step path; the
    # megastep drains up to K per row
    drained = sum(s.attrs["tokens"] for s in spans if s.name == "decode.drain")
    assert drained == eng.row_tokens


@pytest.mark.parametrize("megastep_k", [0, 4])
def test_prefill_chunks_carry_their_request(megastep_k):
    eng, reqs, spans = traced(megastep_k)
    chunks = [s for s in spans if s.name == "prefill.chunk"]
    assert chunks and all("uid" in s.attrs for s in chunks)
    for r in reqs:
        mine = [s.attrs["C"] for s in chunks if s.attrs["uid"] == r.uid]
        assert sum(mine) == len(r.prompt)
    assert all(s.attrs["C"] + s.attrs["pad"] == eng.prefill_chunk
               for s in chunks)
    admitted = [u for s in spans if s.name == "sched.admit"
                for u in s.attrs["uids"]]
    assert sorted(admitted) == [r.uid for r in reqs]


@pytest.mark.parametrize("megastep_k", [0, 4])
def test_decode_dispatch_counts_live_pages(megastep_k):
    """Each ``decode.dispatch`` carries ``live_pages``: ceil(length /
    page) summed over the decoding rows, where a row's length is its prompt
    and the tokens it has drained (the position of the token it feeds,
    plus one)."""
    eng = engine(megastep_k)
    eng.spans.enable()
    want, open_span = [], eng.spans.open

    def open_noting_lengths(name, **attrs):
        if name == "decode.dispatch":
            want.append(sum(-(-(len(r.prompt) + len(r.out)) // eng.page_size)
                            for r in eng.slots if r is not None))
        return open_span(name, **attrs)

    eng.spans.open = open_noting_lengths
    serve(eng)
    spans = [s for s in eng.spans.take() if s.name == "decode.dispatch"]
    assert [s.attrs["live_pages"] for s in spans] == want
    assert any(s.attrs["live_pages"] > s.attrs["rows"] for s in spans)


@pytest.mark.parametrize("megastep_k", [0, 4])
def test_program_obtain_spans_match_the_counter(megastep_k):
    eng, _, spans = traced(megastep_k)
    got = [s for s in spans if s.name == "program.obtain"]
    assert len(got) == eng.stats["programs_obtained"]
    progs = {s.attrs["program"] for s in got}
    assert "prefill_chunk" in progs
    assert ("decode_megastep" if megastep_k else "decode_step") in progs
    # prompts of 5, 8 and 11 tokens in chunks of 4: tails 1 and 3 pad to
    # the full chunk, so one admission executable serves all three
    assert eng.stats["prefill_exe_misses"] == 1
    assert eng.stats["prefill_pad_tokens"] == 3 + 1
    by_id = {s.sid: s for s in spans}
    assert all(by_id[s.parent].name in ("prefill.chunk", "decode.dispatch")
               for s in got)


def test_lowered_programs_carry_their_names():
    eng = engine()
    B, C = eng.batch_slots, eng.prefill_chunk
    pre = eng._prefill_exe(C).lower(
        eng.params, jnp.zeros((1, C), jnp.int32), jnp.asarray(0, jnp.int32),
        eng.caches, jnp.asarray(0, jnp.int32))
    assert pre.as_text().startswith("module @jit_prefill_chunk ")
    z = jnp.zeros(B, jnp.int32)
    mega = eng._megastep_exe(4).lower(eng.params, z, z, jnp.zeros(B, bool),
                                      z, z, z, eng.caches)
    assert mega.as_text().startswith("module @jit_decode_megastep ")


def test_paged_attention_lowers_under_its_name():
    from repro.kernels.paged_attention import paged_attention_impl
    B, G, R, hd, P, M = 2, 2, 2, 8, 4, 3
    n_pages = B * M + 1

    def decode_megastep(q, kp, vp, ppos, block, pos):
        return paged_attention_impl(q, kp, vp, ppos, block, pos,
                                    interpret=True)

    args = (jnp.zeros((B, G, R, hd)), jnp.zeros((n_pages, P, G, hd)),
            jnp.zeros((n_pages, P, G, hd)),
            jnp.zeros((n_pages, P), jnp.int32),
            jnp.zeros((B, M), jnp.int32), jnp.zeros((B,), jnp.int32))
    text = jax.jit(decode_megastep).lower(*args).as_text(debug_info=True)
    assert "paged_attention_decode" in text


def test_close_takes_open_children_with_it():
    log = SpanLog(cap=4)
    log.enable()
    root = log.open("engine.step")
    log.open("decode.drain")            # left open: an exception passed
    log.close(root, step=1)
    spans = log.take()
    assert [s.name for s in spans] == ["decode.drain", "engine.step"]
    assert spans[0].parent == spans[1].sid and spans[1].attrs == {"step": 1}
    for _ in range(6):
        log.close(log.open("sched.admit"))
    assert len(log.take()) == 4 and log.take() == []    # the newest cap
