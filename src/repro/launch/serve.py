"""Open-loop serving driver: Poisson arrivals into the continuous-batching
engine, with the Pliant control loop (monitor -> controller -> variant
hot-swap) closed over per-token latency.

Serving variants come from the explorer's serving-applicable grid — one
source of truth with the colocation benchmarks, ordered precise-first.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-27b-smoke \
      --requests 16 --slots 4 --max-new 12 --rate 50 --qos-target 0.05

``--qos-target 0`` disables control (pin a variant with ``--variant``);
``--mesh 2x4`` serves sharded over an 8-device (data, model) mesh.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.controller import ControllerConfig
from repro.core.explorer import explore
from repro.core.monitor import LatencyMonitor
from repro.core.runtime import PliantRuntime
from repro.core.variants import VariantTable
from repro.launch.compile_cache import use_compile_cache
from repro.models import api
from repro.roofline import DEFAULT_TARGET
from repro.serve.engine import Request, ServeEngine


def serving_table(cfg: ModelConfig, *, slots: int, max_len: int,
                  max_loss: float = 0.05,
                  page_occupancy: float = None,
                  price_from_compile: bool = False, dtype=None,
                  target: str = DEFAULT_TARGET) -> VariantTable:
    """The serving VariantTable for one engine shape, from the explorer.

    ``page_occupancy``: expected live-page fraction of a paged engine —
    prices decode HBM by live pages so the frontier sees paged savings.
    ``price_from_compile`` anchors that pricing on the compiled decode
    cell's bytes (``explorer.decode_kv_share``) instead
    of the coarse heuristic — one extra compile, so opt-in — with params
    and caches in ``dtype`` (the engine's). ``target`` is the device kind
    the explorer prices for."""
    shape = ShapeConfig("serve", max_len, slots, "decode")
    kv_share = None
    if price_from_compile and page_occupancy is not None:
        from repro.core.explorer import decode_kv_share
        kv_share = decode_kv_share(cfg, slots, max_len, dtype=dtype)
    return explore(cfg, shape, serving=True, max_loss=max_loss,
                   page_occupancy=page_occupancy, kv_share=kv_share,
                   target=target)


def percentiles(lat, ps=(50, 95, 99)):
    if not lat:
        return {p: float("nan") for p in ps}
    a = np.asarray(lat, float)
    return {p: float(np.percentile(a, p)) for p in ps}


def parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="gemma2-27b-smoke")
    p.add_argument("--layers", type=int, default=0,
                   help="cut the model to its first N layers, widths kept "
                        "(0 = the configuration's own depth)")
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float32"],
                   help="params and cache pool dtype (float32 for "
                        "bit-parity checks against another layout)")
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-new", type=int, default=12)
    p.add_argument("--max-len", type=int, default=64)
    p.add_argument("--prompt-len", type=int, default=6)
    p.add_argument("--prefill-chunk", type=int, default=16)
    p.add_argument("--rate", type=float, default=0.0,
                   help="Poisson arrival rate (req/s); 0 = all at t=0")
    p.add_argument("--qos-target", type=float, default=0.0,
                   help="per-token latency QoS target (s); 0 = no control")
    p.add_argument("--decision-interval", type=float, default=0.25)
    p.add_argument("--variant", default=None,
                   help="pin a variant by name (e.g. int8); default precise "
                        "or Pliant-controlled when --qos-target is set")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--mesh", default="",
                   help="serve sharded, e.g. 2x4 -> (data=2, model=4)")
    p.add_argument("--paged", action="store_true",
                   help="paged page-pool caches with prefix reuse and the "
                        "pool_pages Pliant knob (default: dense rings)")
    p.add_argument("--page-size", type=int, default=8)
    p.add_argument("--pool-pages", type=int, default=0,
                   help="physical pages (0 = auto-size)")
    p.add_argument("--shared-prefix", type=int, default=0,
                   help="first N prompt tokens identical across requests "
                        "(exercises the prefix cache under --paged)")
    p.add_argument("--megastep", type=int, default=0,
                   help="fuse up to K decode steps per dispatch (lax.scan "
                        "megastep: on-device sampling + EOS/budget stop "
                        "masking, async double-buffered host loop); paged "
                        "only, 0 = one dispatch per token")
    p.add_argument("--eos-id", type=int, default=-1,
                   help="stop-token id; a request emitting it finishes "
                        "early (-1 = generate max-new tokens)")
    p.add_argument("--sync-timing", action="store_true",
                   help="drain every megastep before dispatching the next: "
                        "no pipeline overlap, but per-token stamps measure "
                        "compute instead of dispatch enqueue (benchmarks)")
    p.add_argument("--no-donate", action="store_true",
                   help="keep cache buffers undonated (XLA double-buffers "
                        "the pool; for debugging stale-reference holds)")
    p.add_argument("--max-admission-chunks", type=int, default=4,
                   help="prefill-chunk burst per step when no decoder is "
                        "inside its QoS guard band (continuous batching)")
    p.add_argument("--qos-guard", type=float, default=0.25,
                   help="guard band: burst admission chunks only while "
                        "monitor p99 <= (1 - guard) * QoS target")
    p.add_argument("--chaos", default="",
                   help="capacity-event script for the fault injector, "
                        "e.g. 'revoke@20+4:2,restore@60' (dist.elastic "
                        "grammar: kind@step[+grace][:count])")
    p.add_argument("--admission-timeout", type=float, default=0.0,
                   help="reject a queued request after waiting this many "
                        "seconds without admission (0 = wait forever)")
    p.add_argument("--seed", type=int, default=0)
    return p


@dataclass
class Served:
    """What one ``serve`` run built and what came of it."""
    engine: ServeEngine
    requests: List[Request]
    summary: Dict[str, object]


def serve(argv=None, *, on_step: Optional[Callable] = None) -> Served:
    """Build the engine the CLI describes, drive its requests to the end and
    print the report. ``on_step(engine, step)`` runs after every engine step
    (a driver's hook for mid-run actions, such as a forced variant swap).

    Params and the cache pool share ``--dtype`` (bf16 by default, so a
    published-width model fits one chip)."""
    args = parser().parse_args(argv)
    cfg = get_config(args.arch)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    dtype = jnp.dtype(args.dtype)
    params = api.init(cfg, jax.random.PRNGKey(args.seed), dtype)
    occupancy = (min(1.0, (args.prompt_len + args.max_new) / args.max_len)
                 if args.paged else None)
    dev = jax.devices()[0]
    target = dev.device_kind if dev.platform == "tpu" else DEFAULT_TARGET
    table = serving_table(cfg, slots=args.slots, max_len=args.max_len,
                          page_occupancy=occupancy,
                          price_from_compile=args.paged, dtype=dtype,
                          target=target)
    names = [v.name for v in table.variants]

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        shape = tuple(int(x) for x in args.mesh.split("x"))
        assert len(shape) == 2, "--mesh must be DxM (data x model)"
        mesh = make_mesh(shape, ("data", "model"))

    runtime = None
    if args.qos_target > 0:
        # tail-estimate floor scaled to engine width: one step contributes at
        # most `slots` samples, and slow (compile-heavy) steps mean a decision
        # window may span a single step — don't let the estimator starve
        monitor = LatencyMonitor(qos_target_s=args.qos_target, window=1024,
                                 min_samples=min(20, max(4, 2 * args.slots)))
        runtime = PliantRuntime(table, monitor, ControllerConfig(
            decision_interval_s=args.decision_interval))
    eng = ServeEngine(cfg, batch_slots=args.slots, max_len=args.max_len,
                      params=params, table=table, runtime=runtime,
                      temperature=args.temperature, mesh=mesh,
                      prefill_chunk=args.prefill_chunk, seed=args.seed,
                      cache_dtype=dtype,
                      paged=args.paged, page_size=args.page_size,
                      n_pages=args.pool_pages,
                      max_admission_chunks=args.max_admission_chunks,
                      qos_guard=args.qos_guard,
                      admission_timeout_s=args.admission_timeout,
                      megastep_k=args.megastep, eos_id=args.eos_id,
                      sync_timing=args.sync_timing,
                      donate=not args.no_donate)
    del params                       # the engine holds (or resharded) them
    print(f"dispatch: {eng.explain_dispatch()}")
    print(f"dispatch: {eng.explain_prefill_dispatch()}")
    print(f"dispatch: {eng.explain_megastep()}")
    injector = None
    if args.chaos:
        from repro.dist import elastic
        injector = elastic.FaultInjector.parse(args.chaos)
        print(f"chaos: {injector.pending()} scripted capacity events "
              f"({args.chaos})")
    if args.variant is not None:
        eng.set_variant(names.index(args.variant))

    rng = np.random.default_rng(args.seed)
    shared = list(rng.integers(1, cfg.vocab_size,
                               min(args.shared_prefix, args.prompt_len)))
    reqs = [Request(i, prompt=shared + list(rng.integers(
                        1, cfg.vocab_size, args.prompt_len - len(shared))),
                    max_new=args.max_new) for i in range(args.requests)]
    arrivals = (np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
                if args.rate > 0 else np.zeros(args.requests))

    t0 = time.perf_counter()
    nxt, steps = 0, 0
    while not all(r.done or r.rejected for r in reqs) and steps < 100_000:
        now = time.perf_counter() - t0
        while nxt < len(reqs) and arrivals[nxt] <= now:
            reqs[nxt].t_arrival = t0 + arrivals[nxt]
            eng.submit(reqs[nxt])
            nxt += 1
        if injector is not None:
            for ev in injector.due(steps):
                print(f"chaos@{steps}: {ev.kind} count={ev.count} "
                      f"quanta={ev.quanta} grace={ev.deadline_steps}")
                eng.inject(ev)
        if eng.idle:                 # queue, in-flight admission, slots all empty
            if nxt < len(reqs):      # open loop: idle until the next arrival
                time.sleep(min(arrivals[nxt] - now, 0.01))
                continue
            break
        eng.step()
        steps += 1
        if on_step is not None:
            on_step(eng, steps)
    wall = time.perf_counter() - t0

    # per-token latency seen by each request (inter-token gap; first token's
    # gap runs from arrival, so it includes queueing + admission prefill)
    tok_lat, ttft, queue_wait, admit_compute = [], [], [], []
    for r in reqs:
        if not r.token_times:
            continue
        ts = [r.t_arrival or r.t_admit] + r.token_times
        tok_lat.extend(b - a for a, b in zip(ts, ts[1:]))
        ttft.append(r.token_times[0] - ts[0])
        if r.t_arrival and r.t_admit_start:
            # now that prefill interleaves with decode (paged stall-free
            # loop), the old arrival->completion delta mixed three things;
            # report queue WAIT (arrival -> first chunk issued) separately
            # from admission COMPUTE (pure prefill executable time)
            queue_wait.append(r.t_admit_start - r.t_arrival)
        if r.t_admit:
            admit_compute.append(r.admit_compute_s)
    done = sum(r.done for r in reqs)
    toks = sum(len(r.out) for r in reqs)
    pct = percentiles(tok_lat)
    viol = (float(np.mean(np.asarray(tok_lat) > args.qos_target))
            if args.qos_target > 0 and tok_lat else 0.0)
    print(f"variants: {names} (active={names[eng.active_variant]}, "
          f"priced for {table.target})")
    print(f"{done}/{len(reqs)} requests, {toks} tokens in {wall:.2f}s "
          f"({toks / max(wall, 1e-9):.1f} tok/s, rate={args.rate}/s)")
    ttft95 = float(np.percentile(ttft, 95)) if ttft else float("nan")
    q95 = float(np.percentile(queue_wait, 95)) if queue_wait else 0.0
    a95 = float(np.percentile(admit_compute, 95)) if admit_compute else 0.0
    print(f"per-token latency ms: p50={1e3 * pct[50]:.1f} "
          f"p95={1e3 * pct[95]:.1f} p99={1e3 * pct[99]:.1f}  "
          f"ttft p95={1e3 * ttft95:.1f}  queue-wait p95={1e3 * q95:.1f}  "
          f"admit-compute p95={1e3 * a95:.1f}")
    if args.paged:
        s = eng.pool.stats
        looks = s["prefix_hits"] + s["prefix_misses"]
        chunks = [c for c, _ in eng.step_admission_chunks]
        print(f"paged: pages={eng.pool.spec.n_pages} "
              f"occupancy={eng.pool.occupancy():.2f} "
              f"peak_used={s['peak_used']} "
              f"prefix_hit_rate={s['prefix_hits'] / max(looks, 1):.2f} "
              f"tokens_skipped={s['tokens_skipped']} "
              f"reclaim_events={s['reclaim_events']}")
        print(f"admission: grouped_pages={s['grouped_pages']} "
              f"grouped_fallbacks={s['grouped_fallbacks']} "
              f"replenish_evictions={s['replenish_evictions']} "
              f"chunks/step max={max(chunks, default=0)} "
              f"budget_cap={args.max_admission_chunks}")
    if args.megastep:
        d_t = eng.row_dispatches / max(eng.row_tokens, 1)
        print(f"megastep: k={args.megastep} "
              f"decode_dispatches={eng.decode_dispatches} "
              f"dispatches/token={d_t:.2f} "
              f"drain_block_s={eng.drain_block_s:.3f}")
    if args.qos_target > 0:
        acts = [h["action"] for h in runtime.history if h["action"] != "hold"]
        print(f"qos: target={1e3 * args.qos_target:.1f}ms "
              f"violation_rate={viol:.3f} swaps={eng.swaps} actions={acts}")
    if args.chaos or args.admission_timeout > 0:
        s = eng.stats
        rehomes = [e for e in eng.elastic_log if "mesh_shape" in e]
        print(f"elastic: events={s['capacity_events']} "
              f"rehomes={s['rehomes']} "
              f"collective_retries={s['collective_retries']} "
              f"recovery_steps={[e['recovery_steps'] for e in rehomes]} "
              f"rejected={len(eng.rejected)} "
              f"timeouts={s['admission_timeouts']} "
              f"backoff_skips={s['backoff_skips']}")
        for r in eng.rejected:
            rej = r.rejection
            print(f"  rejected uid={rej.uid} waited={rej.waited_s:.3f}s "
                  f"queue_depth={rej.queue_depth} step={rej.step}")
    summary = dict(requests=len(reqs), done=done,
                   rejected=sum(r.rejected for r in reqs),
                   unfinished=[r.uid for r in reqs
                               if not (r.done or r.rejected)],
                   tokens=toks, wall_s=wall, steps=steps,
                   token_latency_s=pct, ttft_p95_s=ttft95)
    return Served(eng, reqs, summary)


def main(argv=None) -> int:
    """CLI entry point: 0 when every request finished or was rejected by the
    admission timeout it was given, 1 otherwise."""
    use_compile_cache()
    served = serve(argv)
    left = served.summary["unfinished"]
    if left:
        print(f"error: {len(left)} requests neither done nor rejected: "
              f"uids {left}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
