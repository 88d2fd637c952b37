"""End-to-end training driver with the Pliant runtime as a first-class
feature.

Runs REAL training (CPU-sized configs here; the same code path drives the
production mesh on TPU): data pipeline -> per-variant AOT-compiled train
steps -> Pliant monitor/controller switching variants at step boundaries ->
async checkpointing with elastic restore.

  PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b-smoke \
      --steps 200 --batch 8 --seq 128 [--pliant] [--contention trace.json]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.approx.knobs import ApproxKnobs, PRECISE
from repro.configs import get_config
from repro.core.colocation import SERVICES
from repro.core.explorer import explore
from repro.core.monitor import LatencyMonitor
from repro.core.runtime import PliantRuntime
from repro.core.tenant import TrainTenant
from repro.core.variants import VariantTable
from repro.ckpt.checkpoint import CheckpointManager
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.models import api
from repro.train import optim, step as step_mod


def build_variant_steps(cfg, table: VariantTable, opt_cfg, remat="none",
                        mesh=None):
    def factory(knobs: ApproxKnobs):
        fn = step_mod.make_train_step(cfg, knobs, opt_cfg=opt_cfg,
                                      remat=remat, mesh=mesh)
        return jax.jit(fn, donate_argnums=(0, 1))
    table.compile_all(factory)


def train(argv=None) -> float:
    """Run the training job the CLI describes; returns the mean loss of
    the last 10 steps."""
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="phi4-mini-3.8b-smoke")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--pliant", action="store_true",
                   help="enable the Pliant runtime with a synthetic "
                        "contention trace on the token-serve service")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-period", type=int, default=50)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--decision-interval", type=float, default=0.5)
    p.add_argument("--pod-mesh", action="store_true",
                   help="lay local devices out as a (pod, data) mesh so the "
                        "sync_period/grad_compress knobs exercise the real "
                        "cross-pod collectives (needs >=2 devices, e.g. "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    p.add_argument("--chaos", default="",
                   help="capacity-event script for the fault injector, e.g. "
                        "'revoke@40:2,restore@120' — revocations live-shrink "
                        "the train mesh (mid-flight optimizer-state reshard "
                        "+ variant recompile), restores grow it back")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    cfg = get_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = api.init(cfg, key, jnp.float32)
    opt = optim.init_opt(params)
    opt_cfg = optim.OptConfig(lr=args.lr, warmup=20, total_steps=args.steps)

    mesh = None
    if args.pod_mesh:
        if jax.device_count() >= 2:
            from repro.launch.mesh import make_mesh
            n = jax.device_count()
            mesh = make_mesh((2, n // 2), ("pod", "data"))
        else:
            print("WARNING: --pod-mesh ignored (1 device; set XLA_FLAGS="
                  "--xla_force_host_platform_device_count=N) — pod "
                  "collectives will be no-ops")

    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    table = explore(cfg, shape, serving=False, max_variants=4)
    build_variant_steps(cfg, table, opt_cfg, mesh=mesh)

    monitor = LatencyMonitor(SERVICES["token-serve"].qos_target_s)
    # the train job as a first-class Tenant (no elastic reshard actuator on
    # a single host, so its quanta budget is 0 — variant knob only); the
    # same tenant drops into launch/colocate.py's multi-tenant arbiter
    tenant = TrainTenant(table, name="train")
    runtime = PliantRuntime(monitor=monitor, tenants=[tenant])
    runtime.cfg.decision_interval_s = args.decision_interval

    # --chaos: TrainTenant live shrink — the checkpoint-time elastic reshard
    # (save unsharded-logical, re-device_put on any mesh) applied MID-FLIGHT
    # to (params, optimizer state), without the disk round-trip, plus a
    # variant-table recompile on the surviving mesh
    chaos = None
    live = {"params": None, "opt": None, "mesh": mesh, "lost": set()}
    if args.chaos:
        from repro.dist import elastic
        chaos = elastic.FaultInjector.parse(args.chaos)
        base_mesh = mesh

        def on_capacity(ev):
            if ev.kind == elastic.REVOKE:
                if base_mesh is None:
                    print("chaos: revoke ignored (single device, no mesh)")
                    return
                ids = ev.devices or elastic.pick_revoked(
                    base_mesh, ev.count, already=tuple(live["lost"]))
                live["lost"].update(ids)
            elif ev.kind == elastic.RESTORE:
                if ev.devices:
                    live["lost"].difference_update(ev.devices)
                else:
                    live["lost"].clear()
            else:
                return      # quota/collective events: pressure-only here
            if live["lost"]:
                new_mesh, why = elastic.surviving_mesh(base_mesh,
                                                       live["lost"])
                if new_mesh is None:
                    print(f"chaos: cannot shrink ({why}) — degrading via "
                          "the variant ladder only")
                    return
            else:
                new_mesh, why = base_mesh, "full mesh restored"
            t = time.time()
            live["params"], live["opt"] = elastic.reshard_live(
                (live["params"], live["opt"]))
            build_variant_steps(cfg, table, opt_cfg, mesh=new_mesh)
            live["mesh"] = new_mesh
            shape_s = "1x1" if new_mesh is None else \
                "x".join(str(v) for v in new_mesh.shape.values())
            print(f"chaos: resharded (params+opt) onto {shape_s} in "
                  f"{time.time() - t:.2f}s ({why}; lost={sorted(live['lost'])})")

        tenant.elastic_fn = on_capacity
        print(f"chaos: {chaos.pending()} scripted capacity events "
              f"({args.chaos})")

    data_cfg = DataConfig(cfg.vocab_size, args.seq, args.batch,
                          seed=args.seed)
    source = SyntheticLM(data_cfg)
    start_step = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, period=args.ckpt_period)
        if args.resume:
            restored, rstep = mgr.restore_latest((params, opt))
            if restored is not None:
                params, opt = restored
                start_step = rstep
                print(f"resumed from step {rstep}")
    prefetch = Prefetcher(lambda s: source.batch(s), start_step)

    losses = []
    svc = SERVICES["token-serve"]
    t0 = time.time()
    for i in range(start_step, args.steps):
        if chaos is not None:
            due = chaos.due(i)
            if due:
                live["params"], live["opt"] = params, opt
                for ev in due:
                    print(f"chaos@{i}: {ev.kind} count={ev.count} "
                          f"quanta={ev.quanta}")
                    runtime.inject(ev)
                params, opt = live["params"], live["opt"]
        step_idx, tokens = next(prefetch)
        batch = {"tokens": jnp.asarray(tokens)}
        if cfg.family == "encdec":
            batch["frames"] = jax.random.normal(
                jax.random.fold_in(key, i),
                (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
        if cfg.family == "vlm":
            batch["prefix_embeds"] = jax.random.normal(
                jax.random.fold_in(key, i),
                (args.batch, cfg.n_prefix_tokens, cfg.d_model), jnp.float32)
        step_fn = runtime.step_executable() if args.pliant \
            else table.executable(0)
        params, opt, metrics = step_fn(params, opt, batch)
        losses.append(float(metrics["loss"]))
        active_knobs = table.variants[runtime.active_variant].knobs \
            if args.pliant else PRECISE
        if active_knobs.sync_period > 1 \
                and (i + 1) % active_knobs.sync_period == 0:
            # sync-elision knob: the step carries no cross-pod collectives;
            # the driver syncs params every k steps (no-op without a pod axis)
            params = step_mod.pod_sync(params, live["mesh"])
        if args.pliant:
            # synthetic contention trace: mid-run interference burst on the
            # colocated interactive service
            phase = (i - start_step) / max(args.steps - start_step, 1)
            burst = 1.0 if 0.3 < phase < 0.7 else 0.0
            v = table.variants[runtime.active_variant]
            interf = burst * (svc.sens_mem * v.pressure.hbm
                              + svc.sens_ici * v.pressure.ici)
            p99 = svc.p99(0.775, interf, runtime.reclaimed)
            rng = np.random.default_rng(i)
            for x in p99 / 3.2 * np.exp(0.45 * rng.standard_normal(64)):
                monitor.record(float(x))
            runtime.maybe_decide()
        if mgr is not None:
            mgr.maybe_save((params, opt), i + 1)
        if (i + 1) % 20 == 0:
            v = table.variants[runtime.active_variant].name if args.pliant \
                else "precise"
            print(f"step {i+1:5d} loss {np.mean(losses[-20:]):.4f} "
                  f"variant={v} reclaimed={runtime.reclaimed} "
                  f"({(time.time()-t0)/ (i+1-start_step):.2f}s/step)")
    prefetch.close()
    if mgr is not None:
        mgr.save_sync((params, opt), args.steps)
        mgr.wait()
    print(f"final loss {np.mean(losses[-10:]):.4f} "
          f"(first-10 {np.mean(losses[:10]):.4f})")
    if args.pliant:
        switches = [h for h in runtime.history if h["action"] != "hold"]
        print(f"pliant actions: {len(switches)} "
              f"{[h['action'] for h in switches[:8]]}")
    return np.mean(losses[-10:])


def main(argv=None) -> int:
    """CLI entry point: 0 when training ends on a finite loss."""
    use_compile_cache()
    return 0 if np.isfinite(train(argv)) else 1


if __name__ == "__main__":
    raise SystemExit(main())
