"""Paged cache subsystem: block-table engine equivalence, copy-on-write
prefix reuse, and the Pliant-reclaimable page pool.

The paged engine must reproduce the model-level token-by-token greedy
reference EXACTLY across the attention / local+global / hybrid / pure-SSM
cache families, including multi-wave slot reuse (stale-state hazards: reused
pages' positions, reused slots' Mamba state). A shared-prefix workload must
HIT the prefix index and skip the covered prefill chunks; a pool shrink /
regrow round-trip — manual and controller-driven — must never corrupt a
live request.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.approx.knobs import PRECISE, ApproxKnobs
from repro.configs import get_config
from repro.core.controller import ControllerConfig
from repro.core.monitor import LatencyMonitor
from repro.core.runtime import PliantRuntime
from repro.core.variants import Variant, VariantTable
from repro.launch.serve import serving_table
from repro.models import api
from repro.serve.engine import Request, ServeEngine
from tests._reference import greedy_warmup_ref

_PARAMS = {}


def setup(name):
    cfg = get_config(name + "-smoke")
    if name not in _PARAMS:
        _PARAMS[name] = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, _PARAMS[name]


def drive(cfg, params, prompts, max_new=5, *, page_size=4, n_pages=0,
          slots=2, max_len=64, chunk=3, **kw):
    eng = ServeEngine(cfg, batch_slots=slots, max_len=max_len, params=params,
                      prefill_chunk=chunk, page_size=page_size,
                      n_pages=n_pages, **kw)
    reqs = [Request(i, prompt=list(p), max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    return [r.out for r in reqs], eng


def reference(cfg, params, prompts, max_new=5, max_len=64):
    return [greedy_warmup_ref(cfg, params, list(p), max_new, max_len)
            for p in prompts]


@pytest.mark.parametrize("name", ["phi4-mini-3.8b",     # attention
                                  "zamba2-2.7b",        # hybrid (+shared)
                                  "mamba2-780m",        # pure SSM
                                  "gemma2-27b"])        # local+global attn
def test_paged_matches_dense_engine(name):
    cfg, params = setup(name)
    rng = np.random.default_rng(3)
    # 5 requests through 2 slots: multiple admission waves reuse slots AND
    # (with the tight 16-page pool) recycle freed physical pages
    prompts = [list(rng.integers(1, cfg.vocab_size, 7)) for _ in range(5)]
    want = reference(cfg, params, prompts)
    paged, eng = drive(cfg, params, prompts, n_pages=16)
    assert paged == want, (name, paged, want)
    assert eng.pool.stats["frees"] > 0          # pages actually cycled
    assert eng.pool.used == 0 or eng.pool.index  # only prefix pins remain


@pytest.mark.parametrize("name", ["phi4-mini-3.8b", "zamba2-2.7b"])
def test_prefix_reuse_skips_chunks(name):
    """Shared-prompt traffic: later requests map the registered prefix pages
    copy-on-write and skip those prefill chunks entirely (SSM state restored
    from the boundary snapshot for hybrid archs) — with outputs still equal
    to the token-by-token reference's."""
    cfg, params = setup(name)
    rng = np.random.default_rng(7)
    prefix = list(rng.integers(1, cfg.vocab_size, 8))
    prompts = [prefix + list(rng.integers(1, cfg.vocab_size, 4))
               for _ in range(4)]
    prompts.append(list(prompts[0]))            # exact duplicate prompt
    want = reference(cfg, params, prompts)
    paged, eng = drive(cfg, params, prompts)
    assert paged == want, (paged, want)
    s = eng.pool.stats
    # requests 1-3 share the 8-token (2-page) prefix; request 4 additionally
    # matches request 0's full pages capped at len-1 -> still 8 tokens
    assert s["prefix_hits"] >= 4, s
    assert s["tokens_skipped"] >= 4 * 8, s
    # shared pages are refcounted, not copied: peak usage stays well under
    # 5 requests' worth of private pages (3 pages each + decode growth)
    assert s["peak_used"] < 5 * 3 + 3, s


def test_prefix_hit_runs_fewer_chunks():
    """A prefix hit must SKIP executable calls, not just relabel them."""
    cfg, params = setup("phi4-mini-3.8b")
    rng = np.random.default_rng(11)
    prompt = list(rng.integers(1, cfg.vocab_size, 9))
    eng = ServeEngine(cfg, batch_slots=1, max_len=64, params=params,
                      prefill_chunk=2, page_size=4)
    calls = []
    orig = eng._prefill_exe

    def counting(C):
        calls.append(C)
        return orig(C)

    eng._prefill_exe = counting
    eng.submit(Request(0, prompt=list(prompt), max_new=2))
    eng.run()
    first = sum(calls)
    assert first == 9, calls                    # full prompt prefilled
    calls.clear()
    eng.submit(Request(1, prompt=list(prompt), max_new=2))
    eng.run()
    # 8 of 9 tokens (two full pages, capped at len-1) skipped on the hit
    assert sum(calls) == 1, calls


def test_pool_shrink_regrow_roundtrip():
    """A manual pool_pages shrink/regrow mid-decode never corrupts live
    requests: outputs stay equal to the token-by-token reference's."""
    cfg, params = setup("zamba2-2.7b")
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(1, cfg.vocab_size, 7)) for _ in range(4)]
    want = reference(cfg, params, prompts, max_new=10)
    eng = ServeEngine(cfg, batch_slots=2, max_len=64, params=params,
                      prefill_chunk=3, page_size=4)
    reqs = [Request(i, prompt=list(p), max_new=10)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()                              # requests mid-decode
    eng.pool.set_reclaimed(eng.pool.max_quanta)
    for _ in range(3):
        eng.step()                              # decode under shrunk budget
    eng.pool.set_reclaimed(0)
    eng.run()
    assert [r.out for r in reqs] == want
    assert eng.pool.stats["reclaim_events"] == 2
    log = eng.pool.stats["reclaim_log"]
    assert [e["action"] for e in log] == ["shrink", "grow"]


def test_admit_pins_hit_pages_before_alloc_can_evict():
    """Under budget pressure, admit's fresh-page allocation may LRU-evict
    the very prefix entry it just matched; the hit pages must be pinned by
    the slot FIRST so they are never freed/scrubbed/double-allocated while
    the admission maps them."""
    from repro.serve.pages import PagePool, PageSpec
    spec = PageSpec(page_size=4, n_pages=16, max_pages=4)   # usable: 15
    pool = PagePool(spec, batch_slots=2, reclaim_quantum=9)
    prompt_a = list(range(13))                              # 4 pages each
    prompt_b = list(range(100, 113))
    for slot, prompt in ((0, prompt_a), (1, prompt_b)):
        plan = pool.admit(slot, prompt, "tag")
        for b in plan.register:                             # entries at 4/8/12
            pool.register_prefix(slot, prompt, "tag", b)
        pool.free_slot(slot)                                # index-pinned only
    assert pool.used == 6                                   # 3 pages per prefix
    pool.set_reclaimed(1)      # limit 15-9 = 6 == used: nothing evicted YET
    # the hit entry (prompt_a, LRU-oldest) is evicted by _alloc's pressure
    # loop DURING this admission; its pages must already carry the slot's ref
    plan = pool.admit(0, prompt_a, "tag")
    assert plan is not None and plan.shared_tokens == 12
    assert not pool.index                                   # everything evicted
    mapped = [int(p) for p in pool.blocks[0] if p]
    assert len(mapped) == 4
    # every mapped page stayed live: none free, none awaiting a ppos scrub
    assert not (set(mapped) & set(pool.free)), (mapped, list(pool.free))
    assert not (set(mapped) & set(pool.scrub_pending))
    assert all(pool.ref[p] == 1 for p in mapped)
    # and a fresh _alloc never hands out a mapped page
    got = pool._alloc(for_live=True)
    assert got not in mapped


def test_blocked_admission_does_not_inflate_prefix_stats():
    """A pool-blocked request retried every engine step must not bump the
    hit/miss counters (BENCH_serve's prefix_hit_rate) until it commits."""
    from repro.serve.pages import PagePool, PageSpec
    spec = PageSpec(page_size=4, n_pages=8, max_pages=4)
    pool = PagePool(spec, batch_slots=2)
    assert pool.admit(0, list(range(13)), "tag") is not None
    pool.ensure_decode_page(0, 13)
    for _ in range(5):                          # retried while pool is full
        assert pool.admit(1, list(range(16)), "tag") is None
    assert pool.stats["blocked_admissions"] == 5
    assert pool.stats["prefix_hits"] + pool.stats["prefix_misses"] == 1


def test_never_fitting_prompt_raises_instead_of_spinning():
    """A prompt needing more pages than the pool owns must fail loudly at
    admission, not busy-spin run() through max_steps unserved."""
    from repro.serve.pages import PagePool, PageSpec
    pool = PagePool(PageSpec(page_size=4, n_pages=8, max_pages=16),
                    batch_slots=1)
    with pytest.raises(RuntimeError, match="pages but the pool has"):
        pool.admit(0, list(range(33)), "tag")       # 9 pages > 7 usable


def test_registration_bounded_by_max_register_pages():
    """Index growth and (hybrid) snapshot pauses are capped per prompt:
    boundaries past max_register_pages are not registered, and lookups
    still hit the capped depth."""
    from repro.serve.pages import PagePool, PageSpec
    pool = PagePool(PageSpec(page_size=4, n_pages=32, max_pages=8),
                    batch_slots=2, max_register_pages=2)
    prompt = list(range(26))                        # 6 full pages
    plan = pool.admit(0, prompt, "tag")
    assert plan.register == [4, 8]                  # capped at 2 boundaries
    assert pool.stats["register_capped"] == 1
    for b in plan.register:
        pool.register_prefix(0, prompt, "tag", b)
    assert len(pool.index) == 2
    plan2 = pool.admit(1, prompt, "tag")
    assert plan2.shared_tokens == 8                 # deepest registered page


def test_controller_driven_pool_reclaim():
    """pool_pages as the runtime's reclaimable knob: a QoS violation at the
    most-approximate variant RECLAIMs pool quanta (prefix cache evicted
    first, live requests untouched); slack RETURNs them before stepping
    toward precise — and a request served after the regrow matches the
    precise token-by-token reference."""
    cfg, params = setup("gemma2-27b")
    table = serving_table(cfg, slots=4, max_len=64)
    monitor = LatencyMonitor(qos_target_s=1e-7, window=256, min_samples=4)
    runtime = PliantRuntime(table, monitor,
                            ControllerConfig(decision_interval_s=0.0))
    eng = ServeEngine(cfg, batch_slots=4, max_len=64, params=params,
                      runtime=runtime, page_size=8)
    assert runtime.cfg.max_reclaim == eng.pool.max_quanta > 0
    reqs = [Request(i, prompt=[3 + i, 11, 7], max_new=10) for i in range(8)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    acts = [h["action"] for h in runtime.history]
    assert "set_most_approx" in acts and "reclaim_chips" in acts, acts
    assert eng.pool.stats["reclaim_events"] >= 1
    assert eng.pool.reclaimed > 0
    assert all(r.done and len(r.out) == 10 for r in reqs), \
        "reclaim must not corrupt live requests"

    monitor.qos_target_s = 1e9                  # slack: return pages, then
    guard = 0                                   # step back toward precise
    while (eng.active_variant != 0 or runtime.reclaimed > 0) and guard < 30:
        more = [Request(100 + guard * 10 + i, prompt=[2 + i, 5], max_new=8)
                for i in range(4)]
        for r in more:
            eng.submit(r)
        eng.run()
        guard += 1
    assert eng.active_variant == 0 and eng.pool.reclaimed == 0, \
        runtime.history
    assert "return_chips" in [h["action"] for h in runtime.history]

    late = Request(999, prompt=[9, 8, 7], max_new=6)
    eng.submit(late)
    eng.run()
    assert late.out == greedy_warmup_ref(cfg, params, late.prompt, 6)


def test_stall_free_admission_bounds_decoder_gaps():
    """A 32-chunk prompt admitted mid-run must not stall concurrent
    decoders: the engine runs AT MOST ONE admission chunk between decode
    executions (the stall-free budget), and every request's outputs still
    match the token-by-token reference."""
    cfg, params = setup("phi4-mini-3.8b")
    rng = np.random.default_rng(13)
    chunk = 2
    long_prompt = list(rng.integers(1, cfg.vocab_size, 32 * chunk))
    shorts = [list(rng.integers(1, cfg.vocab_size, 5)) for _ in range(2)]

    eng = ServeEngine(cfg, batch_slots=3, max_len=96, params=params,
                      prefill_chunk=chunk, page_size=4)
    events = []

    def recording(make, event):
        def made(*a):
            fn = make(*a)
            return lambda *args: (events.append(event), fn(*args))[1]
        return made

    eng._prefill_exe = recording(eng._prefill_exe, "chunk")
    eng._megastep_exe = recording(eng._megastep_exe, "decode")
    reqs = [Request(i, prompt=list(p), max_new=50)
            for i, p in enumerate(shorts)]
    for r in reqs:
        eng.submit(r)
    for _ in range(6):
        eng.step()                      # shorts admitted and mid-decode
    big = Request(9, prompt=list(long_prompt), max_new=4)
    eng.submit(big)
    eng.run()
    assert all(r.done for r in reqs + [big])
    paged = [r.out for r in reqs + [big]]
    want = [greedy_warmup_ref(cfg, params, r.prompt, r.max_new, 96)
            for r in reqs + [big]]
    assert paged == want, (paged, want)
    # after the first decode, no two admission chunks back-to-back: a long
    # prompt costs active decoders at most one chunk per token
    tail = events[events.index("decode"):]
    assert "decode" in tail and "chunk" in tail
    for a, b in zip(tail, tail[1:]):
        assert not (a == "chunk" and b == "chunk"), tail


def test_window_pages_freed_keeps_occupancy_flat():
    """Banded-only arch on a long decode: pages that fall out of the
    attention window are freed at window-exit boundaries, so pool occupancy
    stays FLAT instead of growing with generation length — and freeing dead
    pages never changes outputs."""
    import dataclasses
    from repro.configs.base import LOCAL_ATTN
    base = get_config("gemma2-27b-smoke")
    cfg = dataclasses.replace(base, name="banded-smoke",
                              pattern=(LOCAL_ATTN,), n_layers=2, window=8)
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(17)
    prompt = list(rng.integers(1, cfg.vocab_size, 10))

    def run(window_free):
        eng = ServeEngine(cfg, batch_slots=1, max_len=128, params=params,
                          prefill_chunk=5, page_size=4)
        assert eng._window_free == (cfg.window if window_free else 0) or \
            not window_free
        if not window_free:
            eng._window_free = 0
        req = Request(0, prompt=list(prompt), max_new=80)
        eng.submit(req)
        live = []
        while not req.done:
            eng.step()
            if eng.slots[0] is not None:
                live.append(eng.pool.live_slot_pages())
        return req.out, live, eng

    out_free, live, eng = run(window_free=True)
    out_keep, live_keep, _ = run(window_free=False)
    assert out_free == out_keep                   # freed pages were dead
    assert eng.pool.stats["window_freed"] > 0
    # steady state: window pages + the write page, NOT position/page_size
    steady = live[len(live) // 2:]
    bound = cfg.window // eng.page_size + 2
    assert max(steady) <= bound, (max(steady), bound)
    assert max(steady) - min(steady) <= 1         # flat
    assert max(live_keep) > bound                 # without freeing it grows
    # total pool usage = live pages + index-pinned prefix pages, also flat
    pinned = sum(len(e.pages) for e in eng.pool.index.values())
    assert eng.pool.used <= bound + pinned


def test_prefill_exe_cache_knob_keyed_and_bounded():
    """Admission executables are keyed by knobs (table entries with equal
    admission knobs share one compiled chunk cell), LRU-bounded, and evicted
    on variant retirement only when no live variant shares the knobs; a
    retired variant's megastep executables go at once."""
    cfg, params = setup("phi4-mini-3.8b")
    int8 = ApproxKnobs(matmul_precision="int8")
    table = VariantTable([Variant(PRECISE, 1.0, 0.0),
                          Variant(int8, 0.8, 0.01),
                          Variant(int8, 0.7, 0.02)])   # same admission knobs
    eng = ServeEngine(cfg, batch_slots=2, max_len=32, params=params,
                      table=table)
    eng._prefill_exe(4)
    eng._megastep_exe(1)
    eng.set_variant(1)
    eng._prefill_exe(4)
    eng._megastep_exe(1)
    eng.set_variant(2)
    eng._prefill_exe(4)                         # shares variant 1's cell
    eng._megastep_exe(1)
    assert len(eng._prefills) == 2
    assert eng._prefill_exe(4) is eng._prefill_exe(4)

    eng.set_variant(0)
    eng.retire_variant(2)                       # variant 1 still uses int8
    assert any(k[0] == int8 for k in eng._prefills)
    eng.retire_variant(1)                       # last int8 user retired
    assert not any(k[0] == int8 for k in eng._prefills)
    assert not any(v in (1, 2) for v, _ in eng._megasteps), eng._megasteps
    assert (0, 1) in eng._megasteps

    # the LRU bound, on a paged SSM hybrid, whose admission programs stay
    # keyed per chunk length (its state would absorb pad tokens)
    hcfg, hparams = setup("zamba2-2.7b")
    hyb = ServeEngine(hcfg, batch_slots=2, max_len=32, params=hparams,
                      page_size=4, max_prefill_exes=2)
    for c in (1, 2, 3, 5):
        hyb._prefill_exe(c)
    assert hyb.stats["prefill_exe_misses"] == 4
    assert len(hyb._prefills) <= 2


def test_paged_engine_multi_device(subproc):
    """8-device mesh: slot-affinity layout — pool page dim AND block-table
    slot dim sharded over the same batch axes, kv_heads over "model" when
    divisible; outputs equal the single-device paged engine, prefix hits
    included."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.models.attention import PagedKVCache
from repro.serve.engine import Request, ServeEngine

cfg = get_config("phi4-mini-3.8b-smoke")
params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
rng = np.random.default_rng(1)
prefix = list(rng.integers(1, cfg.vocab_size, 8))
prompts = [prefix + list(rng.integers(1, cfg.vocab_size, 3))
           for _ in range(6)]

def run(mesh):
    eng = ServeEngine(cfg, batch_slots=4, max_len=32, params=params,
                      mesh=mesh, prefill_chunk=3, page_size=8)
    reqs = [Request(i, prompt=list(p), max_new=5)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng, [r.out for r in reqs]

eng_ref, ref = run(None)
eng_sh, got = run(make_mesh((2, 4), ("data", "model")))
assert got == ref, (got, ref)
pg = [c for c in eng_sh.caches if isinstance(c, PagedKVCache)]
assert pg
for c in pg:
    # slot-affinity: pages split over the batch axes (device-local to their
    # slots' shard); smoke kv_heads don't divide the model axis -> replicated
    assert c.kp.sharding.spec == P(None, "data", None, None, None), \\
        c.kp.sharding
    assert c.block.sharding.spec == P(None, "data", None), c.block.sharding
# prefix namespaces are per-shard (pages must stay device-local): 6
# shared-prefix requests over 2 shards pay one cold miss per shard
assert eng_sh.pool.stats["prefix_hits"] >= 4
print("PAGED_DIST_OK")
""", devices=8)
    assert "PAGED_DIST_OK" in out
