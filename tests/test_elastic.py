"""Deflation-grade elasticity (dist.elastic + engine/runtime wiring).

Covers the fault-injection substrate (deterministic schedules, the CLI
grammar, seeded chaos scripts), the surviving-mesh shrink policy (pinned
model axes, batch axes shrinking outermost-first, slot-affinity divisor
preference), the engine-side capacity actuations on a single device
(quota cuts on the page pool, transient collective-failure retries with
token parity, admission timeout + bounded backoff), the runtime's
capacity-pressure arm of the Fig. 3 hysteresis, corruption-tolerant
checkpoint restore, and — in an 8-device subprocess — the headline
guarantee: revoking 2 of 8 devices mid-decode and restoring them later
completes every request with tokens identical to the unfaulted run.
"""
import numpy as np
import pytest

from repro.dist import elastic
from repro.dist.elastic import CapacityEvent, FaultInjector


# ------------------------------------------------------------- injector --

def test_parse_grammar():
    inj = FaultInjector.parse(
        "revoke@20+5:2, restore@60, quota_cut@10:3, quota_restore@40, "
        "fail@15:2")
    evs = {(e.kind, e.step): e for e in inj._events}
    assert inj.pending() == 5
    r = evs[(elastic.REVOKE, 20)]
    assert r.count == 2 and r.deadline_steps == 5 and r.quanta == 0
    assert evs[(elastic.RESTORE, 60)].count == 0
    q = evs[(elastic.QUOTA_CUT, 10)]
    assert q.quanta == 3 and q.count == 0
    assert evs[(elastic.COLLECTIVE_FAILURE, 15)].count == 2
    with pytest.raises(AssertionError):
        FaultInjector.parse("explode@3")


def test_due_pops_in_step_then_schedule_order():
    inj = FaultInjector([CapacityEvent(elastic.RESTORE, 5),
                         CapacityEvent(elastic.REVOKE, 2, count=1),
                         CapacityEvent(elastic.QUOTA_CUT, 2, quanta=1)])
    assert inj.due(1) == []
    got = inj.due(4)
    assert [e.kind for e in got] == [elastic.REVOKE, elastic.QUOTA_CUT]
    assert inj.pending() == 1
    # a skipped-over step still delivers (driver loops may jump steps)
    assert [e.kind for e in inj.due(100)] == [elastic.RESTORE]
    assert inj.due(200) == [] and len(inj.delivered) == 3


def test_random_script_is_seed_deterministic():
    a = FaultInjector.random_script(n_rounds=3, max_step=50, n_devices=8,
                                    seed=7)
    b = FaultInjector.random_script(n_rounds=3, max_step=50, n_devices=8,
                                    seed=7)
    c = FaultInjector.random_script(n_rounds=3, max_step=50, n_devices=8,
                                    seed=8)
    assert a._events == b._events
    assert a._events != c._events
    kinds = [e.kind for e in a._events]
    assert kinds == [elastic.REVOKE, elastic.RESTORE] * 3
    steps = [e.step for e in a._events]
    assert steps == sorted(steps)
    for ev in a._events:
        if ev.kind == elastic.REVOKE:
            assert 1 <= ev.count <= 4


def test_capacity_event_validation():
    with pytest.raises(AssertionError):
        CapacityEvent("nonsense", 0)
    with pytest.raises(AssertionError):
        CapacityEvent(elastic.REVOKE, -1)


# ------------------------------------------------------- mesh shrinking --

def test_surviving_mesh_policy(subproc):
    out = subproc("""
import jax
from repro.dist import elastic
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))

# count-only revocation picks the highest-ordinal tail, skipping already-
# revoked ids, so survivors stay a contiguous prefix
assert elastic.pick_revoked(mesh, 2) == (6, 7)
assert elastic.pick_revoked(mesh, 1, already=(7,)) == (6,)
assert elastic.pick_revoked(mesh, 0) == ()

# nothing revoked: the mesh comes back unchanged
same, why = elastic.surviving_mesh(mesh, set())
assert same is mesh and why == "nothing revoked"

# 2 of 8 gone: model axis (2) is pinned, data shrinks 4 -> 3; with the
# slot-affinity preference (batch_slots=4) it lands on 2 (a divisor of 4
# costing <= half) using the survivor prefix
m, why = elastic.surviving_mesh(mesh, {6, 7}, prefer_divisor_of=4)
assert dict(m.shape) == {"data": 2, "model": 2}, m.shape
ids = sorted(int(d.id) for d in m.devices.ravel())
assert ids == [0, 1, 2, 3], ids
m2, _ = elastic.surviving_mesh(mesh, {6, 7})   # no preference: take all 6
assert dict(m2.shape) == {"data": 3, "model": 2}, m2.shape

# survivors cannot carry the pinned model axes -> (None, reason)
m3, why3 = elastic.surviving_mesh(mesh, set(range(1, 8)))
assert m3 is None and "pinned" in why3, (m3, why3)

# (pod, data) training mesh: pod shrinks FIRST (outermost batch axis)
tm = make_mesh((2, 4), ("pod", "data"))
m4, _ = elastic.surviving_mesh(tm, {5, 6, 7})
assert dict(m4.shape) == {"pod": 1, "data": 4}, m4.shape
print("MESH_POLICY_OK")
""", devices=8)
    assert "MESH_POLICY_OK" in out


def test_reshard_live_round_trip():
    import jax.numpy as jnp
    tree = {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones(4)}
    out = elastic.reshard_live(tree)
    assert np.allclose(np.asarray(out["w"]), np.arange(12.0).reshape(3, 4))
    staged = elastic.host_stage(tree)
    assert isinstance(staged["b"], np.ndarray)


# ------------------------------------------ engine capacity actuations --

def _setup(name="phi4-mini-3.8b"):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import api
    cfg = get_config(name + "-smoke")
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def _serve(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run()
    return [r.out for r in reqs]


def test_collective_failure_retries_preserve_tokens():
    from repro.serve.engine import Request, ServeEngine
    cfg, params = _setup()
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(1, cfg.vocab_size, 6)) for _ in range(3)]

    def run(faults):
        eng = ServeEngine(cfg, batch_slots=2, max_len=32, params=params,
                          page_size=4, prefill_chunk=4)
        reqs = [Request(i, prompt=p, max_new=5) for i, p in
                enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        while not eng.idle:
            if faults and eng.step_count == 3:
                eng.inject(CapacityEvent(elastic.COLLECTIVE_FAILURE, 0,
                                         count=2))
            eng.step()
        return eng, [r.out for r in reqs]

    ref_eng, ref = run(False)
    eng, got = run(True)
    assert got == ref, "a retried step must commit the same tokens"
    assert eng.stats["collective_retries"] == 2
    assert ref_eng.stats["collective_retries"] == 0
    assert any(e.get("kind") == elastic.COLLECTIVE_FAILURE
               for e in eng.elastic_log)


def test_quota_cut_is_separate_from_reclaim_ledger():
    from repro.serve.engine import Request, ServeEngine
    cfg, params = _setup()
    eng = ServeEngine(cfg, batch_slots=2, max_len=32, params=params,
                      page_size=4, prefill_chunk=4)
    pool = eng.pool
    base_limit = pool.limit
    eng.inject(CapacityEvent(elastic.QUOTA_CUT, 0, quanta=1))
    eng.step()                      # events apply at the step boundary
    assert pool.capacity_cut == 1 and pool.reclaimed == 0
    assert pool.limit == base_limit - pool.quantum
    assert pool.stats["capacity_cut_events"] == 1
    # the arbiter's own ledger composes on top of the external floor
    pool.set_reclaimed(1)
    assert pool.limit == base_limit - 2 * pool.quantum
    pool.set_reclaimed(0)
    eng.inject(CapacityEvent(elastic.QUOTA_RESTORE, 0))
    eng.step()
    assert pool.capacity_cut == 0 and pool.limit == base_limit
    # the pool still serves traffic end to end after the round trip
    r = Request(0, prompt=[5, 9, 2, 7], max_new=4)
    assert _serve(eng, [r]) and r.done
    pool.assert_consistent()


def test_revoke_without_mesh_is_pressure_only():
    from repro.serve.engine import Request, ServeEngine
    cfg, params = _setup()
    eng = ServeEngine(cfg, batch_slots=2, max_len=32, params=params,
                      page_size=4)
    eng.inject(CapacityEvent(elastic.REVOKE, 0, count=1))
    r = Request(0, prompt=[3, 1, 4], max_new=4)
    _serve(eng, [r])
    assert r.done
    assert any(e.get("ignored") == "no mesh" for e in eng.elastic_log)


def test_admission_timeout_rejects_structurally():
    import time
    from repro.serve.engine import Request, ServeEngine
    cfg, params = _setup()
    eng = ServeEngine(cfg, batch_slots=1, max_len=64, params=params,
                      prefill_chunk=4, admission_timeout_s=0.0005)
    first = Request(0, prompt=[3, 1, 4], max_new=12)
    eng.submit(first)
    eng.step()                              # first occupies the only slot
    late = Request(1, prompt=[2, 7, 1], max_new=4)
    eng.submit(late)
    time.sleep(0.002)
    eng.run()
    assert first.done and len(first.out) == 12
    assert late.rejected and not late.done and not late.out
    rej = late.rejection
    assert rej is not None and rej.uid == 1 and rej.waited_s > 0
    assert rej.queue_depth >= 1 and rej.step > 0
    assert eng.rejected == [late]
    assert eng.stats["admission_timeouts"] == 1
    # rejection is never silent drop: the driver loop's completion predicate
    # (done or rejected) must see every request resolved
    assert all(r.done or r.rejected for r in (first, late))


def test_blocked_admission_backs_off_then_recovers():
    from repro.serve.engine import Request, ServeEngine
    cfg, params = _setup()
    rng = np.random.default_rng(4)
    prompt = list(rng.integers(1, cfg.vocab_size, 6))

    ref_eng = ServeEngine(cfg, batch_slots=2, max_len=32, params=params,
                          page_size=4, prefill_chunk=4)
    ref = Request(0, prompt=list(prompt), max_new=5)
    _serve(ref_eng, [ref])

    eng = ServeEngine(cfg, batch_slots=2, max_len=32, params=params,
                      page_size=4, prefill_chunk=4)
    # external quota grab floors the budget to zero: admissions block
    eng.pool.set_capacity_cut(eng.pool.max_quanta + eng.pool.spec.usable)
    req = Request(0, prompt=list(prompt), max_new=5)
    eng.submit(req)
    for _ in range(12):
        eng.step()
    assert not req.done and req.uid in eng._backoff
    assert eng.stats["backoff_skips"] > 0, \
        "a blocked request must not hammer the allocator every step"
    blocked = eng.pool.stats["blocked_admissions"]
    assert 0 < blocked < 12, \
        (blocked, "backoff must skip most of the 12 retry opportunities")
    eng.inject(CapacityEvent(elastic.QUOTA_RESTORE, 0))
    eng.pool.set_capacity_cut(0)
    eng.run()
    assert req.done and req.out == ref.out
    assert req.uid not in eng._backoff


# ------------------------------------------------- runtime integration --

def test_capacity_pressure_forces_violation_arm():
    from repro.approx.knobs import PRECISE, ApproxKnobs
    from repro.core.controller import Action, ControllerConfig
    from repro.core.monitor import LatencyMonitor
    from repro.core.runtime import PliantRuntime
    from repro.core.variants import Variant, VariantTable
    table = VariantTable([
        Variant(PRECISE, 1.0, 0.0),
        Variant(ApproxKnobs(matmul_precision="int8"), 0.7, 0.003)])
    monitor = LatencyMonitor(qos_target_s=1e9, min_samples=4)
    rt = PliantRuntime(table, monitor,
                       ControllerConfig(decision_interval_s=0.0))
    monitor.record_many(np.full(8, 0.5))    # way under target: deep slack
    assert rt.maybe_decide() in (Action.HOLD, Action.STEP_PRECISE)

    rt.notify_capacity(CapacityEvent(elastic.REVOKE, 0, count=2))
    assert rt.capacity_pressure
    monitor.record_many(np.full(8, 0.5))    # still slack by latency alone
    act = rt.maybe_decide()
    assert act == Action.SET_MOST_APPROX and rt.active_variant == 1, \
        "outstanding capacity loss must enter the violation arm"
    assert rt.history[-1]["violated"] and not rt.history[-1]["slack"]
    assert rt.history[-1]["capacity"] == 1

    rt.notify_capacity(CapacityEvent(elastic.RESTORE, 0))
    assert not rt.capacity_pressure
    monitor.record_many(np.full(8, 0.5))
    rt.maybe_decide()                       # slack arm reachable again
    assert rt.active_variant == 0
    assert [e["kind"] for e in rt.capacity_log] == [elastic.REVOKE,
                                                    elastic.RESTORE]


def test_runtime_inject_fans_out_to_tenants():
    from repro.core.tenant import TrainTenant
    from repro.core.monitor import LatencyMonitor
    from repro.core.runtime import PliantRuntime
    from repro.core.variants import Variant, VariantTable
    from repro.approx.knobs import PRECISE
    table = VariantTable([Variant(PRECISE, 1.0, 0.0)])
    seen = []
    t = TrainTenant(table, name="train", elastic_fn=seen.append)
    rt = PliantRuntime(monitor=LatencyMonitor(1.0), tenants=[t])
    ev = CapacityEvent(elastic.REVOKE, 3, count=1)
    rt.inject(ev)
    assert seen == [ev] and rt.capacity_pressure


# --------------------------------------------------- checkpoint safety --

def test_restore_latest_skips_corrupt_checkpoints(tmp_path, capsys):
    from repro.ckpt import checkpoint as ckpt
    tree = {"w": np.arange(6.0).reshape(2, 3), "s": np.float32(3.0)}
    ckpt.save(tmp_path / "step_10", tree, 10)
    ckpt.save(tmp_path / "step_20",
              {"w": tree["w"] + 1, "s": np.float32(4.0)}, 20)
    ckpt.save(tmp_path / "step_30",
              {"w": tree["w"] + 2, "s": np.float32(5.0)}, 30)
    # newest torn mid-write (truncated npz), next-newest has a mangled
    # manifest — both classic kill-mid-copy shapes
    shard = tmp_path / "step_30" / "shard0.npz"
    shard.write_bytes(shard.read_bytes()[: 40])
    (tmp_path / "step_20" / "manifest.json").write_text("{not json")
    # plus a stale stage dir from a kill mid-save: swept at manager init
    stale = tmp_path / ".ckpt_tmp_dead"
    stale.mkdir()
    (stale / "junk").write_text("x")

    mgr = ckpt.CheckpointManager(tmp_path)
    assert not stale.exists()
    restored, step = mgr.restore_latest(tree)
    assert step == 10, "must fall back past BOTH corrupt checkpoints"
    assert np.allclose(restored["w"], tree["w"])
    assert len(mgr.skipped) == 2
    assert "step_30" in mgr.skipped[0] and "step_20" in mgr.skipped[1]
    err = capsys.readouterr().err
    assert err.count("WARNING: skipping corrupt/partial checkpoint") == 2

    # every checkpoint corrupt: (None, None), never a crash
    shard10 = tmp_path / "step_10" / "shard0.npz"
    shard10.write_bytes(b"\x00" * 10)
    mgr2 = ckpt.CheckpointManager(tmp_path)
    restored, step = mgr2.restore_latest(tree)
    assert restored is None and step is None and len(mgr2.skipped) == 3


# --------------------------------------------- 8-device chaos parity  --

def test_revoke_2_of_8_mid_decode_token_parity(subproc):
    """The headline robustness guarantee: a (4, 2) data x model engine that
    loses 2 devices mid-decode (with a grace deadline) and gets them back
    later completes EVERY request with tokens identical to the unfaulted
    run — zero drops, zero corruption — and stamps recovery metrics."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np, json
from repro.configs import get_config
from repro.dist import elastic
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.serve.engine import Request, ServeEngine

cfg = get_config("phi4-mini-3.8b-smoke")
params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
rng = np.random.default_rng(11)
prompts = [list(rng.integers(1, cfg.vocab_size, 7)) for _ in range(8)]

def run(script):
    mesh = make_mesh((4, 2), ("data", "model"))
    eng = ServeEngine(cfg, batch_slots=4, max_len=32, params=params,
                      mesh=mesh, page_size=4, prefill_chunk=3)
    reqs = [Request(i, prompt=list(p), max_new=6)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    inj = elastic.FaultInjector.parse(script) if script else None
    steps = 0
    while not eng.idle and steps < 2000:
        if inj is not None:
            for ev in inj.due(steps):
                eng.inject(ev)
        eng.step()
        steps += 1
    assert eng.idle, "drained"
    return eng, reqs

ref_eng, ref = run("")
eng, got = run("revoke@4+2:2,restore@9")

assert all(r.done for r in got), [r.uid for r in got if not r.done]
assert not eng.rejected, "zero dropped requests"
assert [r.out for r in got] == [r.out for r in ref], "token parity"

rehomes = [e for e in eng.elastic_log if "mesh_shape" in e]
assert len(rehomes) == 2, eng.elastic_log       # shrink + grow
shrink, grow = rehomes
assert shrink["kind"] == "revoke" and shrink["revoked"] == [6, 7]
assert shrink["mesh_shape"] == {"data": 2, "model": 2}, shrink
assert shrink["pages_migrated"] > 0
assert shrink["recovery_steps"] is not None and shrink["recovery_steps"] >= 1
assert grow["kind"] == "restore" and grow["revoked"] == []
assert grow["mesh_shape"] == {"data": 4, "model": 2}, grow
notice = [e for e in eng.elastic_log if e.get("kind") == "revoke_notice"]
assert notice and notice[0]["deadline_step"] == notice[0]["step"] + 2
assert eng.stats["rehomes"] == 2 and eng.stats["capacity_events"] == 2
print("CHAOS_PARITY_OK " + json.dumps(dict(
    recovery_steps=shrink["recovery_steps"],
    pages=shrink["pages_migrated"])))
""", devices=8)
    assert "CHAOS_PARITY_OK" in out
