"""Chunked-prefill admission and Pliant-controlled serving.

Engine-level equivalence: admission via chunked prefill into the page pool
must reproduce the seed token-by-token warmup outputs EXACTLY (greedy) for
the attention, hybrid, and Mamba cache families. Control: a forced QoS
violation must make ``PliantRuntime`` hot-swap the serving variant mid-run —
crossing the ``kv_quant`` boundary both ways — with decode continuing
across the swap.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.controller import ControllerConfig
from repro.core.monitor import LatencyMonitor
from repro.core.runtime import PliantRuntime
from repro.launch.serve import serving_table
from repro.models import api
from repro.models.attention import PagedKVCache
from repro.serve.engine import Request, ServeEngine
from tests._reference import greedy_warmup_ref

_PARAMS = {}


def setup(name):
    cfg = get_config(name + "-smoke")
    if name not in _PARAMS:
        _PARAMS[name] = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, _PARAMS[name]


@pytest.mark.parametrize("name", ["phi4-mini-3.8b",     # attention
                                  "zamba2-2.7b",        # hybrid (+shared)
                                  "mamba2-780m",        # pure SSM
                                  "gemma2-27b"])        # local+global attn
def test_admission_matches_tokenwise_warmup(name):
    cfg, params = setup(name)
    rng = np.random.default_rng(3)
    # prompt (7) > prefill_chunk (3): exercises multi-chunk admission with a
    # ragged tail; 4 requests through 2 slots: reused slots and pages
    eng = ServeEngine(cfg, batch_slots=2, max_len=64, params=params,
                      prefill_chunk=3)
    reqs = [Request(uid, prompt=list(rng.integers(1, cfg.vocab_size, 7)),
                    max_new=5) for uid in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r in reqs:
        want = greedy_warmup_ref(cfg, params, r.prompt, 5)
        assert r.done and r.out == want, (r.uid, r.out, want)


def test_admission_chunk_size_invariance():
    """Outputs must not depend on the admission chunk size."""
    cfg, params = setup("phi4-mini-3.8b")
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(1, cfg.vocab_size, 9)) for _ in range(3)]

    def outs(chunk):
        eng = ServeEngine(cfg, batch_slots=2, max_len=64, params=params,
                          prefill_chunk=chunk)
        reqs = [Request(i, prompt=p, max_new=4) for i, p in
                enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [r.out for r in reqs]

    assert outs(2) == outs(9) == outs(64)


def test_forced_qos_swap_crosses_kvq_boundary():
    cfg, params = setup("gemma2-27b")
    table = serving_table(cfg, slots=4, max_len=64)
    names = [v.name for v in table.variants]
    assert names[0] == "precise" and any(
        v.knobs.kv_quant for v in table.variants), names
    most = len(table) - 1

    # impossible target -> first decision jumps to most-approximate (Fig. 3),
    # crossing the kv_quant boundary with requests mid-decode. min_samples=4:
    # with decision_interval 0 the window resets every step, so the tail
    # estimate must resolve from one step's worth of samples (4 slots).
    # max_reclaim=0: no chips to shuffle before variant steps (single host)
    monitor = LatencyMonitor(qos_target_s=1e-7, window=256, min_samples=4)
    runtime = PliantRuntime(table, monitor,
                            ControllerConfig(decision_interval_s=0.0,
                                             max_reclaim=0))
    eng = ServeEngine(cfg, batch_slots=4, max_len=64, params=params,
                      runtime=runtime)
    reqs = [Request(i, prompt=[3 + i, 11, 7], max_new=10) for i in range(6)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert eng.active_variant == most
    assert eng.swaps and eng.swaps[0][1] == most
    assert eng.swaps[0][0] < len(eng.step_latencies), \
        "swap must happen mid-run, not after drain"
    assert all(r.done and len(r.out) == 10 for r in reqs), \
        "decode must continue across the swap"
    kv = [c for c in eng.caches if isinstance(c, PagedKVCache)]
    assert kv and all(c.kp.dtype == jnp.int8 for c in kv), \
        "crossing into kv_quant must convert the KV pages to int8"
    assert any(h["action"] == "set_most_approx" for h in runtime.history)

    # relax the target -> controller steps back toward precise one variant
    # per decision, crossing the kv_quant boundary in the other direction
    monitor.qos_target_s = 1e9
    guard = 0
    while eng.active_variant != 0 and guard < 20:
        more = [Request(100 + guard * 10 + i, prompt=[2 + i, 5], max_new=10)
                for i in range(4)]
        for r in more:
            eng.submit(r)
        eng.run()
        assert all(r.done for r in more)
        guard += 1
    assert eng.active_variant == 0, runtime.history
    kv = [c for c in eng.caches if isinstance(c, PagedKVCache)]
    assert all(c.kp.dtype == jnp.float32 for c in kv), \
        "leaving kv_quant must convert the KV pages back"
    assert any(h["action"] == "step_toward_precise" for h in runtime.history)

    # a request served entirely under the restored precise variant matches
    # the seed token-by-token warmup exactly
    late = Request(999, prompt=[9, 8, 7], max_new=6)
    eng.submit(late)
    eng.run()
    assert late.out == greedy_warmup_ref(cfg, params, late.prompt, 6)


def test_serving_table_from_explorer():
    """One source of truth: serving variants come from the explorer grid —
    ordered precise-first, no train-only knobs, with serve-side kv_quant."""
    cfg, _ = setup("gemma2-27b")
    table = serving_table(cfg, slots=4, max_len=64)
    assert table.variants[0].knobs.is_precise()
    for v in table.variants:
        assert v.knobs.token_drop == 0 and v.knobs.layer_skip == 0
        assert v.knobs.sync_period == 1 and v.knobs.grad_compress == "none"
    losses = [v.quality_loss for v in table.variants]
    assert losses == sorted(losses)
    assert any(v.knobs.kv_quant for v in table.variants)
