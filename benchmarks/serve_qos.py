"""Serving QoS benchmark: per serving-variant throughput and tail latency of
the continuous-batching engine on the reduced config, one Pliant-controlled
run, and a paged-engine run on a shared-prefix trace (page-pool occupancy,
prefix-cache hit rate, pool reclaim events) — the serve-side perf trajectory
(BENCH_serve.json)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from benchmarks.common import RESULTS_DIR, Rows

ARCH = "gemma2-27b-smoke"
SLOTS, MAX_NEW, MAX_LEN, N_REQ, PROMPT = 4, 8, 32, 8, 6


def _drive(eng, cfg, rng, shared_prefix: int = 0, prompt_len: int = PROMPT,
           max_new: int = MAX_NEW):
    from repro.serve.engine import Request
    shared = list(rng.integers(1, cfg.vocab_size, shared_prefix))
    reqs = [Request(i, prompt=shared + list(
                        rng.integers(1, cfg.vocab_size,
                                     prompt_len - shared_prefix)),
                    max_new=max_new) for i in range(N_REQ)]
    import time
    t0 = time.perf_counter()
    for r in reqs:
        r.t_arrival = time.perf_counter()
        eng.submit(r)
    eng.run()
    wall = time.perf_counter() - t0
    toks = sum(len(r.out) for r in reqs)
    lat = np.asarray(eng.step_latencies, float)
    # queue WAIT (arrival -> first prefill chunk issued) vs admission
    # COMPUTE (pure prefill executable time): now that prefill interleaves
    # with decode in the paged engine, the old arrival->completion p95
    # conflated the two — report both
    qw = [r.t_admit_start - r.t_arrival for r in reqs
          if r.t_admit_start and r.t_arrival]
    ac = [r.admit_compute_s for r in reqs if r.t_admit]
    return {
        "tok_s": toks / max(wall, 1e-9),
        "wall_s": wall,
        "p50_ms": 1e3 * float(np.percentile(lat, 50)),
        "p95_ms": 1e3 * float(np.percentile(lat, 95)),
        "p99_ms": 1e3 * float(np.percentile(lat, 99)),
        "steps": len(lat),
        "queue_wait_p95_ms": 1e3 * float(np.percentile(qw, 95)) if qw else 0.0,
        "admit_compute_p95_ms": (1e3 * float(np.percentile(ac, 95))
                                 if ac else 0.0),
    }


def main(rows: Rows):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.controller import ControllerConfig
    from repro.core.monitor import LatencyMonitor
    from repro.core.runtime import PliantRuntime
    from repro.launch.serve import serving_table
    from repro.models import api
    from repro.serve.engine import ServeEngine

    cfg = get_config(ARCH)
    params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    table = serving_table(cfg, slots=SLOTS, max_len=MAX_LEN)
    out = {}
    for vi, v in enumerate(table.variants):
        eng = ServeEngine(cfg, batch_slots=SLOTS, max_len=MAX_LEN,
                          params=params, table=table, sync_timing=True)
        eng.set_variant(vi)
        stats = _drive(eng, cfg, np.random.default_rng(0))
        out[v.name] = stats
        rows.add(f"serve.{v.name}", 1e3 * stats["p95_ms"],
                 f"tok_s={stats['tok_s']:.1f};p99_ms={stats['p99_ms']:.1f}")
    # QoS target between precise and most-approximate p95: violation rate per
    # variant against one shared target, plus a controlled (hot-swapping) run
    target_s = 0.5 * (out[table.variants[0].name]["p95_ms"]
                      + out[table.variants[-1].name]["p95_ms"]) / 1e3
    for vi, v in enumerate(table.variants):
        eng = ServeEngine(cfg, batch_slots=SLOTS, max_len=MAX_LEN,
                          params=params, table=table, sync_timing=True)
        eng.set_variant(vi)
        _drive(eng, cfg, np.random.default_rng(1))
        lat = np.asarray(eng.step_latencies, float)
        out[v.name]["qos_target_ms"] = 1e3 * target_s
        out[v.name]["violation_rate"] = float(np.mean(lat > target_s))
    monitor = LatencyMonitor(qos_target_s=target_s, window=1024)
    runtime = PliantRuntime(table, monitor,
                            ControllerConfig(decision_interval_s=0.05))
    eng = ServeEngine(cfg, batch_slots=SLOTS, max_len=MAX_LEN, params=params,
                      table=table, runtime=runtime, sync_timing=True)
    stats = _drive(eng, cfg, np.random.default_rng(2))
    stats["swaps"] = eng.swaps
    stats["final_variant"] = table.variants[eng.active_variant].name
    out["pliant"] = stats
    rows.add("serve.pliant", 1e3 * stats["p95_ms"],
             f"tok_s={stats['tok_s']:.1f};swaps={len(eng.swaps)}")

    # paged engine on a shared-prefix Poisson-style trace, Pliant-controlled
    # with an impossible target so the controller walks to most-approximate
    # and then reclaims pool pages: page-pool occupancy, prefix-cache hit
    # rate, and reclaim-event counts are the CI-tracked paged metrics
    monitor = LatencyMonitor(qos_target_s=1e-7, window=256,
                             min_samples=SLOTS)
    # the paged table prices decode HBM by live pages, kv_share anchored on
    # the compiled cell's cost_analysis (explorer.decode_kv_share)
    ptable = serving_table(cfg, slots=SLOTS, max_len=MAX_LEN,
                           page_occupancy=(PROMPT + MAX_NEW) / MAX_LEN,
                           price_from_compile=True)
    runtime = PliantRuntime(ptable, monitor,
                            ControllerConfig(decision_interval_s=0.0))
    eng = ServeEngine(cfg, batch_slots=SLOTS, max_len=MAX_LEN, params=params,
                      runtime=runtime, page_size=4, sync_timing=True)
    stats = _drive(eng, cfg, np.random.default_rng(3),
                   shared_prefix=PROMPT - 2)
    s = eng.pool.stats
    looks = s["prefix_hits"] + s["prefix_misses"]
    stats["pool_pages"] = eng.pool.spec.n_pages
    stats["pool_occupancy_peak"] = s["peak_used"] / eng.pool.spec.usable
    stats["prefix_hit_rate"] = s["prefix_hits"] / max(looks, 1)
    stats["tokens_skipped"] = s["tokens_skipped"]
    stats["reclaim_events"] = s["reclaim_events"]
    stats["swaps"] = eng.swaps
    out["paged"] = stats
    rows.add("serve.paged", 1e3 * stats["p95_ms"],
             f"tok_s={stats['tok_s']:.1f};"
             f"hit_rate={stats['prefix_hit_rate']:.2f};"
             f"reclaims={stats['reclaim_events']}")
    # the megastep engine (K=4) on the paged pool's target workload: a
    # shared system prompt (16-token prompts, 12 shared) with short
    # completions. It runs the same trace twice: a warm-up pass (compiles;
    # prefix registration — the steady state a long-running server sits
    # in) and a measured pass with fresh counters, under sync_timing (drain
    # before stamping) so the latency numbers measure compute, not async
    # dispatch enqueue. CI asserts counts only (dispatches/token < 1, the
    # host share in [0, 1]); speed is the chip benchmark's (bench/).
    cmp_trace = dict(shared_prefix=12, prompt_len=16, max_new=6)
    eng = ServeEngine(cfg, batch_slots=SLOTS, max_len=MAX_LEN,
                      params=params, page_size=4, sync_timing=True,
                      megastep_k=4)
    _drive(eng, cfg, np.random.default_rng(5), **cmp_trace)
    eng.step_latencies.clear()
    eng.step_admission_chunks.clear()
    eng.decode_dispatches = eng.row_dispatches = eng.row_tokens = 0
    eng.drain_block_s = 0.0
    mega = _drive(eng, cfg, np.random.default_rng(5), **cmp_trace)
    mega["mesh_shape"] = dict(eng.mesh.shape) if eng.mesh is not None \
        else None
    mega["sharded_kernel"] = eng.sharded_kernel
    mega["decode_dispatches"] = eng.decode_dispatches
    mega["dispatches_per_token"] = (eng.row_dispatches
                                    / max(eng.row_tokens, 1))
    # fraction of the wall the host spent NOT blocked on device
    # transfers — the megastep pipeline's target metric
    mega["host_overhead_frac"] = max(
        0.0, 1.0 - eng.drain_block_s / max(mega["wall_s"], 1e-9))
    s = eng.pool.stats
    mega["pool_occupancy_peak"] = s["peak_used"] / eng.pool.spec.usable
    mega["grouped_pages"] = s["grouped_pages"]
    mega["admission_chunks_max"] = max(
        (c for c, _ in eng.step_admission_chunks), default=0)
    out["comparison"] = {"megastep": mega}
    rows.add("serve.megastep", 1e3 * mega["p95_ms"],
             f"tok_s={mega['tok_s']:.1f};"
             f"dispatches_per_token={mega['dispatches_per_token']:.2f};"
             f"host_overhead_frac={mega['host_overhead_frac']:.2f}")
    # admission compute per mesh shape: single-device whole-chunk cell vs
    # the ring-sequence-parallel cell on 8 simulated devices (subprocess —
    # device count is fixed at jax import). CI tracks admit_compute_p95
    # and the dispatch string per shape.
    admission = {"1x1": {
        "mesh_shape": None,
        "prefill_dispatch": eng.explain_prefill_dispatch(),
        "admit_compute_p95_ms": mega["admit_compute_p95_ms"],
    }}
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run([sys.executable, "-c", _ADMIT_CHILD],
                          capture_output=True, text=True, env=env)
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("ADMIT_JSON:")), None)
    assert line is not None, (proc.stdout, proc.stderr[-2000:])
    admission["2x4"] = json.loads(line[len("ADMIT_JSON:"):])
    out["admission"] = admission
    for shape, st in admission.items():
        rows.add(f"serve.admission.{shape}", st["admit_compute_p95_ms"],
                 st["prefill_dispatch"])
    # chaos smoke (8 simulated devices, subprocess): revoke 2 of 8 devices
    # mid-decode with a grace deadline, restore them later. The child runs
    # the SAME trace unfaulted first and asserts zero dropped requests and
    # exact greedy token parity — deflation must be invisible to clients —
    # then reports recovery time and QoS during the shrunk window. CI gates
    # on dropped == 0 and token_parity.
    proc = subprocess.run([sys.executable, "-c", _ELASTIC_CHILD],
                          capture_output=True, text=True, env=env)
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith("ELASTIC_JSON:")), None)
    assert line is not None, (proc.stdout, proc.stderr[-2000:])
    est = json.loads(line[len("ELASTIC_JSON:"):])
    out["elastic"] = est
    rows.add("serve.elastic", est["recovery_steps"],
             f"dropped={est['dropped']};parity={est['token_parity']};"
             f"pages={est['pages_migrated']};"
             f"qos_shrink_ms={est['qos_during_shrink_p95_ms']:.1f};"
             f"qos_steady_ms={est['qos_steady_p95_ms']:.1f}")
    (RESULTS_DIR / "BENCH_serve.json").write_text(json.dumps(out, indent=1))
    return rows


# one tiny sharded trace on 8 simulated host devices: the ring-prefill
# admission cell end to end through the paged engine (interpret-mode kernels)
_ADMIT_CHILD = """
import json, time
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.serve.engine import Request, ServeEngine
cfg = get_config("gemma2-27b-smoke")
params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
mesh = make_mesh((2, 4), ("data", "model"))
eng = ServeEngine(cfg, batch_slots=4, max_len=32, params=params, mesh=mesh,
                  page_size=4, prefill_chunk=8,
                  use_kernel=True, kernel_interpret=True)
rng = np.random.default_rng(0)
reqs = [Request(i, prompt=list(rng.integers(1, cfg.vocab_size, 6)),
                max_new=4) for i in range(4)]
for r in reqs:
    r.t_arrival = time.perf_counter()
    eng.submit(r)
eng.run()
ac = [r.admit_compute_s for r in reqs if r.t_admit]
out = {"mesh_shape": dict(eng.mesh.shape),
       "prefill_dispatch": eng.explain_prefill_dispatch(),
       "admit_compute_p95_ms": (1e3 * float(np.percentile(ac, 95))
                                if ac else 0.0)}
print("ADMIT_JSON:" + json.dumps(out))
"""

# the chaos smoke: 8 simulated devices, revoke 2 mid-decode (2-step grace),
# restore later; unfaulted reference run first, parity asserted IN the child
_ELASTIC_CHILD = """
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.dist import elastic
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.serve.engine import Request, ServeEngine

cfg = get_config("gemma2-27b-smoke")
params = api.init(cfg, jax.random.PRNGKey(0), jnp.float32)
rng = np.random.default_rng(7)
prompts = [list(rng.integers(1, cfg.vocab_size, 6)) for _ in range(8)]

def run(script):
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = ServeEngine(cfg, batch_slots=4, max_len=32, params=params,
                      mesh=mesh, page_size=4, prefill_chunk=8,
                      use_kernel=True, kernel_interpret=True)
    reqs = [Request(i, prompt=list(p), max_new=8)
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
    assert eng.idle, "chaos run did not drain"
    return eng, reqs

ref_eng, ref = run("")
# grace deadline lands at step 4 — mid-decode of the first wave, so live
# pages migrate off the revoked shard; restore at 9 re-homes the second wave
eng, got = run("revoke@2+2:2,restore@9")
rehomes = [e for e in eng.elastic_log if "mesh_shape" in e]
assert len(rehomes) == 2, eng.elastic_log
shrink, grow = rehomes
lat = np.asarray(eng.step_latencies, float)
lo, hi = shrink["step_index"], grow["step_index"]
shrunk, steady = lat[lo:hi], np.concatenate([lat[:lo], lat[hi:]])
out = dict(
    dropped=sum(1 for r in got if not r.done) + len(eng.rejected),
    token_parity=bool([r.out for r in got] == [r.out for r in ref]),
    recovery_steps=shrink["recovery_steps"],
    grow_recovery_steps=grow["recovery_steps"],
    pages_migrated=shrink["pages_migrated"],
    cutover_s=shrink["cutover_s"],
    mesh_during_shrink=shrink["mesh_shape"],
    qos_during_shrink_p95_ms=(1e3 * float(np.percentile(shrunk, 95))
                              if len(shrunk) else 0.0),
    qos_steady_p95_ms=1e3 * float(np.percentile(steady, 95)))
assert out["dropped"] == 0 and out["token_parity"], out
print("ELASTIC_JSON:" + json.dumps(out))
"""
