"""Grouped/speculative page allocation and the watermark replenisher.

Admission maps a request's projected decode pages in the SAME free-list
transaction as its prompt pages (all-or-nothing: a request whose decode
pages do not fit waits, so decode never finds the pool empty);
copy-on-write prefix pins survive the
speculative reservation even when the allocation evicts the index; and the
background watermark reservation never strands a page."""
import copy

import pytest

from repro.serve.pages import PagePool, PageSpec


def test_grouped_admit_maps_decode_pages_up_front():
    pool = PagePool(PageSpec(page_size=4, n_pages=32, max_pages=8),
                    batch_slots=2)
    plan = pool.admit(0, list(range(13)), "tag", reserve_tokens=12)
    # 13 prompt tokens = 4 pages; +12 projected decode tokens -> 7 pages
    assert plan is not None and plan.reserved_pages == 3
    assert len(pool.slot_pages[0]) == 7
    assert pool.stats["grouped_admissions"] == 1
    assert pool.stats["grouped_pages"] == 3
    # the decode hot loop finds every projected page already mapped: no
    # per-page-crossing allocation, no block-table re-push
    for pos in (13, 16, 20, 24):
        assert pool.ensure_decode_page(0, pos) is False
    pool.assert_consistent()
    # reserved pages are freed with the slot like any private page
    pool.free_slot(0)
    assert pool.used == 0
    pool.assert_consistent()


def test_grouped_reservation_capped_by_block_table():
    """The group never projects past max_pages — the block table row is the
    hard ceiling, not a reason to fail admission."""
    pool = PagePool(PageSpec(page_size=4, n_pages=32, max_pages=5),
                    batch_slots=1)
    plan = pool.admit(0, list(range(13)), "tag", reserve_tokens=64)
    assert plan is not None and plan.reserved_pages == 1    # 5 - 4 prompt
    assert len(pool.slot_pages[0]) == 5
    pool.assert_consistent()


def test_alloc_n_is_all_or_nothing():
    pool = PagePool(PageSpec(page_size=4, n_pages=8, max_pages=8),
                    batch_slots=2)                          # 7 usable
    assert pool.admit(0, list(range(8)), "tag") is not None  # 2 pages
    before = (list(pool.free), list(pool.ref), pool.used,
              pool.stats["allocs"], list(pool.scrub_pending))
    assert pool._alloc_n(6) is None                         # only 5 free
    after = (list(pool.free), list(pool.ref), pool.used,
              pool.stats["allocs"], list(pool.scrub_pending))
    assert after == before                                  # exact undo
    pool.assert_consistent()
    got = pool._alloc_n(5)                                  # boundary fits
    assert got is not None and len(got) == 5
    assert pool.used == 7


def test_grouped_falls_back_to_prompt_only_under_pressure():
    """Under pressure a grouped admission no longer falls back to mapping
    the prompt alone (its decode could then find no page): it waits."""
    pool = PagePool(PageSpec(page_size=4, n_pages=8, max_pages=8),
                    batch_slots=2)                          # 7 usable
    assert pool.admit(1, list(range(100, 112)), "tag") is not None  # 3 pages
    plan = pool.admit(0, list(range(12)), "tag", reserve_tokens=16)
    # full group (7 pages) no longer fits, though the 3 prompt pages would
    assert plan is None
    assert pool.slot_pages[0] == []
    assert pool.stats["blocked_admissions"] == 1
    assert pool.stats["grouped_admissions"] == 0
    # once the other slot frees its pages the whole group fits
    pool.free_slot(1)
    plan = pool.admit(0, list(range(12)), "tag", reserve_tokens=16)
    assert plan is not None and plan.reserved_pages == 4
    pool.assert_consistent()


def test_cow_pins_survive_speculative_reservation():
    """Under budget pressure the speculative allocation's LRU loop may
    evict the very prefix entry the admission just matched; the hit pages
    must already carry the slot's pin so the copy-on-write mapping stays
    live while the reservation allocates past them."""
    pool = PagePool(PageSpec(page_size=4, n_pages=16, max_pages=8),
                    batch_slots=2, reclaim_quantum=9)       # 15 usable
    prompt_a = list(range(13))                              # 4 pages
    prompt_b = list(range(100, 113))
    for slot, prompt in ((0, prompt_a), (1, prompt_b)):
        pool.admit(slot, prompt, "tag")
        pool.register_prefix(slot, prompt, "tag", 12)       # pins pages 1..3
        pool.free_slot(slot)                                # index-pinned only
    assert pool.used == 6
    pool.set_reclaimed(1)                   # limit 15-9=6 == used: squeezed
    plan = pool.admit(0, prompt_a, "tag", reserve_tokens=8)
    # hit the 3-page shared prefix, then allocate tail + 2 reserved pages
    # through the pressure loop: it evicts prompt_a's entry first (LRU-
    # oldest) — the hit pages survive on the slot's pin — then prompt_b's
    assert plan is not None
    assert plan.shared_tokens == 12 and plan.reserved_pages == 2
    assert not pool.index
    mapped = [int(p) for p in pool.blocks[0] if p]
    assert len(mapped) == 6
    assert not (set(mapped) & set(pool.free)), (mapped, list(pool.free))
    assert not (set(mapped) & set(pool.scrub_pending))
    assert all(pool.ref[p] == 1 for p in mapped)
    pool.assert_consistent()


def test_watermark_replenish_keeps_headroom_without_stranding():
    pool = PagePool(PageSpec(page_size=4, n_pages=16, max_pages=8),
                    batch_slots=3)                          # 15 usable
    prompts = [list(range(i * 100, i * 100 + 13)) for i in range(3)]
    for slot, prompt in enumerate(prompts):
        plan = pool.admit(slot, prompt, "tag")              # 4 pages each
        for b in plan.register:
            pool.register_prefix(slot, prompt, "tag", b)
    pool.free_slot(1)
    pool.free_slot(2)                       # slots 1/2 now index-pinned only
    assert pool.used == 10 and len(pool.free) == 5
    # headroom (5) below the low watermark: evict LRU entries off the
    # admission path — slot 0's entries are slot-pinned (evicting them
    # frees nothing), slot 1's actually release pages — until high
    evicted = pool.replenish(low=6, high=8)
    assert evicted > 0
    assert pool.stats["replenish_evictions"] == evicted
    assert min(len(pool.free), pool.limit - pool.used) >= 6
    pool.assert_consistent()
    # above the watermark: a no-op, not an eviction treadmill
    assert pool.replenish(low=6, high=8) == 0
    # the live slot's pages were untouchable throughout
    assert len(pool.slot_pages[0]) == 4
    pool.free_slot(0)
    while pool.index:                       # drain: nothing may be stranded
        pool.replenish(low=pool.spec.usable, high=pool.spec.usable)
    assert pool.used == 0 and len(pool.free) == pool.spec.usable
    pool.assert_consistent()


def test_replenish_measures_headroom_under_reclaim_limit():
    """Headroom is allocatable room under the RECLAIM limit, not the raw
    free-list length: after a shrink, eviction keeps restoring room (by
    lowering ``used``) even while free pages are plentiful."""
    pool = PagePool(PageSpec(page_size=4, n_pages=24, max_pages=4),
                    batch_slots=2, reclaim_quantum=5)       # 23 usable
    for slot, base in ((0, 0), (1, 100)):
        prompt = list(range(base, base + 13))
        pool.admit(slot, prompt, "tag")
        pool.register_prefix(slot, prompt, "tag", 12)
        pool.free_slot(slot)
    assert pool.used == 6 and len(pool.free) == 17
    pool.set_reclaimed(3)                       # limit 23 - 15 = 8
    # 17 raw free pages, but allocatable room is only limit - used = 2:
    # replenish must evict (the LRU entry, freeing its 3 pages) anyway
    evicted = pool.replenish(low=3, high=4)
    assert evicted == 1
    assert pool.used == 3 and len(pool.index) == 1
    assert min(len(pool.free), pool.limit - pool.used) >= 3
    pool.assert_consistent()
    while pool.index:                           # drain: nothing stranded
        pool.replenish(low=pool.spec.usable, high=pool.spec.usable)
    assert pool.used == 0 and len(pool.free) == pool.spec.usable
    pool.assert_consistent()


def test_admit_that_does_not_fit_returns_none_and_changes_nothing():
    pool = PagePool(PageSpec(page_size=4, n_pages=16, max_pages=8),
                    batch_slots=3)                          # 15 usable
    assert pool.admit(0, list(range(20)), "tag", reserve_tokens=8)  # 7
    assert pool.admit(1, list(range(50, 62)), "tag")                # 3
    state = lambda: copy.deepcopy((
        [list(f) for f in pool._free], list(pool.ref), pool.blocks.tolist(),
        pool.slot_pages, sorted(pool.index), pool.used,
        {k: v for k, v in pool.stats.items() if k != "blocked_admissions"},
        list(pool.scrub_pending)))
    before = state()
    # the prompt's 3 pages fit, prompt and decode (8 pages) do not
    assert pool.admit(2, list(range(100, 112)), "tag",
                      reserve_tokens=20) is None
    assert state() == before
    assert pool.stats["blocked_admissions"] == 1
    pool.assert_consistent()


def test_chained_prefix_pages_count_as_evictable():
    """The chained prefix entries of one prompt share their leading pages
    (each page pinned once per entry): an admission that needs them freed
    must see them as evictable, or it would wait forever on an idle
    pool."""
    pool = PagePool(PageSpec(page_size=4, n_pages=16, max_pages=16),
                    batch_slots=2)                          # 15 usable
    prompt = list(range(40))                                # 10 pages
    plan = pool.admit(0, prompt, "tag")
    for b in plan.register:                   # as the engine's prefill does
        pool.register_prefix(0, prompt, "tag", b)
    pool.free_slot(0)                         # its entries keep them
    assert pool.used == 10 and max(pool.ref) > 1
    plan = pool.admit(1, list(range(500, 540)), "tag", reserve_tokens=12)
    assert plan is not None and len(pool.slot_pages[1]) == 13
    pool.assert_consistent()


def test_full_pool_serves_every_request():
    """The pool fault's witness at test size (4 slots, 39 usable pages,
    prompts 40-200, outputs 4-48, seed 13): with the prompt mapped alone
    when its decode pages did not fit, a decode found the pool full and
    raised 'page pool exhausted mid-decode'."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import api
    from repro.serve.engine import Request, ServeEngine
    cfg = get_config("phi4-mini-3.8b-smoke")
    eng = ServeEngine(cfg, batch_slots=4, max_len=256,
                      params=api.init(cfg, jax.random.PRNGKey(0),
                                      jnp.float32),
                      prefill_chunk=32, page_size=16,
                      n_pages=40, megastep_k=4)
    assert eng.pool.spec.usable == 39
    rng = np.random.default_rng(13)
    reqs = [Request(i, list(map(int, rng.integers(
        1, cfg.vocab_size, int(rng.integers(40, 201))))),
        max_new=int(rng.integers(4, 49))) for i in range(24)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done and len(r.out) == r.max_new for r in reqs)
    assert eng.pool.stats["blocked_admissions"] > 0    # the pool did fill
