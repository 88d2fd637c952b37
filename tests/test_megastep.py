"""Megastep decode: the fused K-token dispatch must be a pure perf knob.

The contract is STREAM invariance, not host-numpy bit parity: greedy
megastep output must equal the model-level token-by-token reference across
all four cache families, and temperature output must be invariant in K
(the on-device sampler keys every draw by (seed, uid, draw_index), so the
megastep width cannot change the stream). EOS inside a megastep must stop
that row in-scan without corrupting siblings or the page pool, and buffer
donation must be verifiably ACTIVE (aliased executables + consumed inputs)
rather than silently dropped.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.serve.engine import Request, ServeEngine
from tests._reference import greedy_warmup_ref

ARCHS = ["phi4-mini-3.8b",     # MHA
         "zamba2-2.7b",        # hybrid attn/SSM (+shared)
         "mamba2-780m",        # pure SSM
         "gemma2-27b"]         # GQA + local attention

_PARAMS = {}


def setup(name):
    cfg = get_config(name + "-smoke")
    if name not in _PARAMS:
        _PARAMS[name] = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, _PARAMS[name]


def drive(cfg, params, prompts, max_new=5, *, slots=2, chunk=3, **kw):
    eng = ServeEngine(cfg, batch_slots=slots, max_len=64, params=params,
                      prefill_chunk=chunk, page_size=4, **kw)
    reqs = [Request(i, prompt=list(p), max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    eng.pool.assert_consistent()
    return [list(r.out) for r in reqs], eng


def prompts_for(cfg, n=5, length=7, seed=3):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, cfg.vocab_size, length)))
            for _ in range(n)]


@pytest.mark.parametrize("name", ARCHS)
@pytest.mark.parametrize("k", [1, 4, 16])
def test_megastep_greedy_matches_per_step(name, k):
    """Greedy megastep(K) == the token-by-token reference, token for token,
    through multiple admission waves (5 requests / 2 slots)."""
    cfg, params = setup(name)
    prompts = prompts_for(cfg)
    base = [greedy_warmup_ref(cfg, params, p, 5) for p in prompts]
    out, eng = drive(cfg, params, prompts, megastep_k=k)
    assert out == base, (name, k, out, base)
    assert eng.decode_dispatches > 0
    # per-row accounting: megastep can only LOWER dispatches/token
    assert eng.row_dispatches / max(eng.row_tokens, 1) <= 1.0


@pytest.mark.parametrize("name", ["zamba2-2.7b", "gemma2-27b"])
def test_megastep_temperature_stream_invariant_in_k(name):
    """On-device temperature sampling: the (seed, uid, draw_index) fold-in
    stream makes the output independent of the megastep width — K=4 and
    K=1 megasteps must emit identical tokens (and a different seed must
    not)."""
    cfg, params = setup(name)
    prompts = prompts_for(cfg, n=4, length=6, seed=7)
    t1, _ = drive(cfg, params, prompts, max_new=6,
                  megastep_k=1, temperature=0.7, seed=11)
    t4, _ = drive(cfg, params, prompts, max_new=6,
                  megastep_k=4, temperature=0.7, seed=11)
    assert t1 == t4
    other, _ = drive(cfg, params, prompts, max_new=6,
                     megastep_k=4, temperature=0.7, seed=12)
    assert other != t4   # the seed actually feeds the stream


@pytest.mark.parametrize("name", ["phi4-mini-3.8b", "mamba2-780m"])
def test_eos_mid_megastep_frees_slot_without_corrupting_siblings(name):
    """A row hitting EOS inside a megastep stops emitting THERE (in-scan
    stop masking), its slot/pages are freed at the drain, and sibling rows
    decode on unperturbed — K=8 equals K=1 under the same eos_id."""
    cfg, params = setup(name)
    prompts = prompts_for(cfg, n=4, length=6, seed=7)
    base, _ = drive(cfg, params, prompts, max_new=6, megastep_k=1)
    # a token observed MID-output in the eos-free run becomes the stop id
    eos = base[0][2]
    e1, _ = drive(cfg, params, prompts, max_new=6, megastep_k=1, eos_id=eos)
    e8, eng = drive(cfg, params, prompts, max_new=6, megastep_k=8,
                    eos_id=eos)
    assert e1 == e8, (eos, e1, e8)
    assert any(o[-1] == eos and len(o) < 6 for o in e8), e8  # early stop
    assert all(o[-1] == eos or len(o) == 6 for o in e8), e8  # nothing past it
    assert eng.pool.slot_pages == [[] for _ in range(eng.batch_slots)]


def test_donation_active_in_compiled_megastep():
    """Donation is an executable property — assert the lowered megastep
    actually aliases input caches to output caches (alias_size > 0), and
    that disabling donation removes the aliasing."""
    from repro.train import step as step_mod
    cfg, params = setup("phi4-mini-3.8b")
    eng = ServeEngine(cfg, batch_slots=2, max_len=64, params=params,
                      prefill_chunk=3, page_size=4, megastep_k=4)
    step = step_mod.make_paged_megastep(cfg, k=4, dynamic_scatter=True)
    B = eng.batch_slots
    zi = jnp.zeros((B,), jnp.int32)
    zb = jnp.zeros((B,), bool)
    args = (params, zi, zi, zb, zi, zi, zi, eng.caches)
    donated = jax.jit(step, donate_argnums=(7,)).lower(*args).compile()
    plain = jax.jit(step).lower(*args).compile()
    assert donated.memory_analysis().alias_size_in_bytes > 0
    assert plain.memory_analysis().alias_size_in_bytes == 0


def test_donation_consumes_stale_cache_references():
    """End-to-end: after a megastep the previous cache buffers are GONE —
    reading a stale reference raises, proving XLA reused the memory
    instead of double-buffering the KV pool."""
    cfg, params = setup("zamba2-2.7b")
    eng = ServeEngine(cfg, batch_slots=2, max_len=64, params=params,
                      prefill_chunk=3, page_size=4, megastep_k=4)
    req = Request(0, prompt=prompts_for(cfg)[0], max_new=6)
    eng.submit(req)
    while not req.out:          # admit until the slot decodes
        eng.step()
    stale = eng.caches
    eng.step()                  # megastep consumes `stale`
    eng.run()
    assert req.done
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(jax.tree.leaves(stale)[0])


def test_donation_off_keeps_buffers_alive():
    """The escape hatch: donate=False engines never consume their inputs
    (tests/tools that hold cache references stay valid)."""
    cfg, params = setup("phi4-mini-3.8b")
    eng = ServeEngine(cfg, batch_slots=2, max_len=64, params=params,
                      prefill_chunk=3, page_size=4,
                      megastep_k=4, donate=False)
    req = Request(0, prompt=prompts_for(cfg)[0], max_new=5)
    eng.submit(req)
    while not req.out:
        eng.step()
    stale = eng.caches
    eng.run()
    np.asarray(jax.tree.leaves(stale)[0])   # must NOT raise
    assert req.done


def test_per_uid_rng_streams_match_fresh_generators():
    """Regression for the cached per-uid numpy streams (`_rng_for`): the
    i-th draw for uid u must equal the i-th draw of a fresh
    default_rng((seed, uid)) — caching generators across calls must not
    advance or cross the streams."""
    cfg, params = setup("phi4-mini-3.8b")
    eng = ServeEngine(cfg, batch_slots=2, max_len=64, params=params,
                      prefill_chunk=3, page_size=4,
                      temperature=0.8, seed=5)
    draws = {}
    for uid in (3, 9, 3, 9, 3):
        g = eng._rng_for(Request(uid, prompt=[1], max_new=1))
        draws.setdefault(uid, []).append(g.random())
    for uid, got in draws.items():
        fresh = np.random.default_rng((5, uid))
        want = [fresh.random() for _ in got]
        assert got == want, (uid, got, want)


def test_megastep_pipeline_survives_variant_swap():
    """Hot-swapping the variant mid-run (across the kv_quant cache-encoding
    boundary, the worst case) with a megastep IN FLIGHT: the executable
    table rebuilds per (variant, K), the rebuilt executable re-donates, and
    every request still completes with full-length output and a consistent
    pool."""
    from repro.approx.knobs import PRECISE, ApproxKnobs
    from repro.core.variants import Variant, VariantTable
    cfg, params = setup("gemma2-27b")
    table = VariantTable([Variant(PRECISE, 1.0, 0.0),
                          Variant(ApproxKnobs(kv_quant=True), 0.8, 0.01)])
    prompts = prompts_for(cfg, n=4, length=6)
    eng = ServeEngine(cfg, batch_slots=2, max_len=64, params=params,
                      prefill_chunk=3, page_size=4,
                      megastep_k=8, table=table)
    reqs = [Request(i, prompt=list(p), max_new=6)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    steps = 0
    while not eng.idle:
        eng.step()
        steps += 1
        if steps == 4:
            eng.request_variant(1)
        assert steps < 500
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 6 for r in reqs)
    assert eng.active_variant == 1
    # the rebuilt table is keyed (variant, k) where k is clamped to the
    # longest remaining row budget — some variant-1 executable must exist
    assert any(v == 1 for (v, _) in eng._megasteps), eng._megasteps.keys()
    eng.pool.assert_consistent()


def test_explain_megastep_banner():
    cfg, params = setup("phi4-mini-3.8b")
    eng = ServeEngine(cfg, batch_slots=2, max_len=64, params=params,
                      page_size=4, megastep_k=6)
    s = eng.explain_megastep()
    assert "6 tokens" in s and "donation ON" in s and "pipeline" in s
    assert "megastep scan" in eng.explain_dispatch()
