import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
ShapeDtypeStruct stand-ins (no allocation), prove it fits via
``memory_analysis()``, and extract roofline inputs (``cost_analysis()`` +
collective bytes parsed from optimized HLO) into a JSON artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mamba2-780m \
      --shape train_4k --mesh pod [--variant int8] [--n-micro 4] \
      [--remat full] [--policy fsdp_tp] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod
"""
import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro import flags
from repro import roofline
from repro.approx.knobs import ApproxKnobs, PRECISE
from repro.configs import SHAPES, get_config, shape_applicable
from repro.dist import sharding
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.train import optim, step as step_mod

VARIANTS = {
    "precise": PRECISE,
    "int8": ApproxKnobs(matmul_precision="int8"),
    "drop25": ApproxKnobs(token_drop=0.25),
    "skip25": ApproxKnobs(layer_skip=0.25),
    "kvstride2": ApproxKnobs(kv_keep_stride=2),
    "topk_half": None,     # resolved per-arch below
    "int8_kvq": ApproxKnobs(matmul_precision="int8", kv_quant=True),
    "gint8": ApproxKnobs(grad_compress="int8"),   # int8-wire pod grad reduce
}


def resolve_variant(name: str, cfg) -> ApproxKnobs:
    if name == "topk_half":
        if cfg.moe is None:
            raise SystemExit(f"{cfg.name} has no MoE top-k knob")
        return ApproxKnobs(topk_override=max(1, cfg.moe.top_k // 2))
    return VARIANTS[name]


def lower_cell(cfg, shape, mesh, knobs, *, policy=None, n_micro=1,
               remat="full"):
    """Returns (lowered, n_chips). Abstract everything: no device arrays."""
    from repro.dist import annotate
    b_spec = sharding.batch_pspec(shape.global_batch, mesh)
    pol = policy or sharding.default_policy(cfg)
    annotate.set_batch_axes(b_spec[0] if len(b_spec) else None,
                            fsdp_axis="data" if pol == "fsdp_tp" else None)
    params_sh = sharding.param_shardings(cfg, mesh, policy)
    abstract_params = api.abstract(cfg)
    in_sh = sharding.input_shardings(cfg, shape, mesh)
    in_specs = api.input_specs(cfg, shape)
    ep_axis = "model" if (cfg.moe is not None and "model" in mesh.shape) \
        else None

    if shape.kind == "train":
        opt_abs = jax.eval_shape(optim.init_opt, abstract_params)
        opt_sh = optim.OptState(
            step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            m=jax.tree.map(lambda s: s, params_sh),
            v=jax.tree.map(lambda s: s, params_sh))
        fn = step_mod.make_train_step(cfg, knobs, n_micro=n_micro,
                                      remat=remat, ep_axis=ep_axis, mesh=mesh,
                                      param_pspecs=params_sh)
        jitted = jax.jit(fn,
                         in_shardings=(params_sh, opt_sh, in_sh),
                         out_shardings=(params_sh, opt_sh, None),
                         donate_argnums=(0, 1))
        with jax.set_mesh(mesh):
            return jitted.lower(abstract_params, opt_abs, in_specs)

    if shape.kind == "prefill":
        fn = step_mod.make_prefill_fn(cfg, knobs, ep_axis=ep_axis, mesh=mesh,
                                      remat=remat)
        jitted = jax.jit(fn, in_shardings=(params_sh, in_sh),
                         out_shardings=None)
        with jax.set_mesh(mesh):
            return jitted.lower(abstract_params, in_specs)

    # decode
    cache_sh, caches_abs = sharding.cache_shardings(cfg, shape, mesh)
    fn = step_mod.make_serve_step(cfg, knobs, ep_axis=ep_axis, mesh=mesh)
    extra, extra_sh = (), ()
    if cfg.family == "encdec":
        enc_spec = in_specs.pop("enc_out")
        enc_sh = in_sh.pop("enc_out")
        extra, extra_sh = (enc_spec,), (enc_sh,)
    arg_sh = (params_sh, in_sh["tokens"], in_sh["position"], cache_sh) \
        + extra_sh
    jitted = jax.jit(fn, in_shardings=arg_sh,
                     out_shardings=(None, cache_sh),
                     donate_argnums=(3,))
    with jax.set_mesh(mesh):
        return jitted.lower(abstract_params, in_specs["tokens"],
                            in_specs["position"], caches_abs, *extra)


def loop_trips(cfg, shape, knobs, n_micro: int, remat: str):
    """Extra-body multipliers per structural loop site (see flags.py).

    Each value is the number of EXTRA copies of that site's loop body present
    in the true program relative to the base compile — nesting-aware: a site
    nested inside loops with total outer trip count T and own trip count n
    contributes T*(n-1) extra bodies, while each enclosing probe's delta
    already carries exactly one copy of the inner body (the algebra closes:
    sum_i mult_i * d_i reconstructs the fully-unrolled cost; validated in
    tests/test_dryrun_accounting.py).
    """
    from repro.approx.knobs import keep_groups
    from repro.models.lm import _near_sqrt_factors
    mult = {}
    g = len(keep_groups(cfg.n_groups, knobs.layer_skip))
    mic = n_micro if shape.kind == "train" else 1
    if mic > 1:
        mult["micro"] = mic - 1
    if remat == "2level" and shape.kind in ("train", "prefill"):
        no, ni = _near_sqrt_factors(g)
        if no > 1:
            mult["groups_outer"] = mic * (no - 1)
            mult["groups"] = mic * no * (ni - 1)
        else:
            mult["groups"] = mic * (g - 1)
    else:
        mult["groups"] = mic * (g - 1)
    if shape.kind == "train":
        from repro.models.lm import ce_chunk
        s_text = shape.seq_len - (cfg.n_prefix_tokens or 0)
        nc_ce = s_text // ce_chunk(s_text)
        if nc_ce > 1:
            mult["ce"] = mic * (nc_ce - 1)
    # (no "ssd" site: the SSD chunk-state recurrence is a static python loop
    # in kernels/ref.py — every chunk body is already in the base compile)
    if cfg.family == "encdec" and shape.kind != "decode":
        if cfg.n_encoder_layers > 1:
            mult["enc"] = mic * (cfg.n_encoder_layers - 1)
    return {k: v for k, v in mult.items() if v > 0}


def _compile_and_measure(cfg, shape, mesh, knobs, *, policy, n_micro, remat):
    t0 = time.time()
    lowered = lower_cell(cfg, shape, mesh, knobs, policy=policy,
                         n_micro=n_micro, remat=remat)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    coll = roofline.collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll,
        "mem": mem,
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str, variant: str,
             *, policy=None, n_micro=1, remat="full", out_dir="results/dryrun",
             tag="", probe_loops=True, probe3=False):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        print(f"SKIP {arch} x {shape_name}: {reason}")
        return {"skipped": reason, "arch": arch, "shape": shape_name}
    knobs = resolve_variant(variant, cfg)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    if knobs.grad_compress != "none" and "pod" not in mesh.shape:
        # without a pod axis the compressed reduce is a no-op and the cell
        # would silently measure identically to precise under a gint8 label
        reason = "grad_compress needs a pod axis (--mesh multipod)"
        print(f"SKIP {arch} x {shape_name} x {variant}: {reason}")
        return {"skipped": reason, "arch": arch, "shape": shape_name}
    n_chips = mesh.size

    flags.reset_unroll()
    base = _compile_and_measure(cfg, shape, mesh, knobs, policy=policy,
                                n_micro=n_micro, remat=remat)
    mults = loop_trips(cfg, shape, knobs, n_micro, remat) if probe_loops \
        else {}
    flops = base["flops"]
    bytes_acc = base["bytes_accessed"]
    coll = dict(base["collectives"])
    probes = {}
    for site, extra in mults.items():
        flags.reset_unroll()
        flags.set_unroll(site, 2)
        p2 = _compile_and_measure(cfg, shape, mesh, knobs, policy=policy,
                                  n_micro=n_micro, remat=remat)
        if probe3:
            # 3-point probe: f(k) = base + k*b + c, where c is a one-time
            # fusion-break cost at the first unroll. Marginal clean body
            # b = f(3) - f(2); the break cost c is added once.
            flags.reset_unroll()
            flags.set_unroll(site, 3)
            p3 = _compile_and_measure(cfg, shape, mesh, knobs, policy=policy,
                                      n_micro=n_micro, remat=remat)
            d_flops = max(p3["flops"] - p2["flops"], 0.0)
            d_bytes = max(p3["bytes_accessed"] - p2["bytes_accessed"], 0.0)
            c_flops = max(p2["flops"] - base["flops"] - d_flops, 0.0)
            c_bytes = max(p2["bytes_accessed"] - base["bytes_accessed"]
                          - d_bytes, 0.0)
            flops += extra * d_flops + c_flops
            bytes_acc += extra * d_bytes + c_bytes
            coll_ref = p2["collectives"]
            coll_d = {k: max(p3["collectives"].get(k, 0.0)
                             - p2["collectives"].get(k, 0.0), 0.0)
                      for k in set(p3["collectives"]) | set(coll_ref)}
        else:
            d_flops = max(p2["flops"] - base["flops"], 0.0)
            d_bytes = max(p2["bytes_accessed"] - base["bytes_accessed"], 0.0)
            flops += extra * d_flops
            bytes_acc += extra * d_bytes
            coll_d = {k: max(p2["collectives"].get(k, 0.0)
                             - base["collectives"].get(k, 0.0), 0.0)
                      for k in set(p2["collectives"])
                      | set(base["collectives"])}
        for k, d in coll_d.items():
            coll[k] = coll.get(k, 0.0) + extra * d
        probes[site] = {"extra": extra, "d_flops": d_flops,
                        "d_bytes": d_bytes, "compile_s": p2["compile_s"],
                        "probe3": probe3}
    flags.reset_unroll()

    mem = base["mem"]
    art = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(mesh.shape), "variant": variant,
        "policy": policy or sharding.default_policy(cfg),
        "n_micro": n_micro, "remat": remat, "n_chips": n_chips,
        "flops": flops,
        "bytes_accessed": bytes_acc,
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "peak_bytes_est": int(mem.argument_size_in_bytes
                              + mem.temp_size_in_bytes
                              + mem.output_size_in_bytes
                              - mem.alias_size_in_bytes),
        "collectives": coll,
        "probes": probes,
        "lower_s": base["lower_s"], "compile_s": base["compile_s"],
    }
    mf = roofline.model_flops(cfg, shape, knobs)
    terms = roofline.terms_from_artifact(art, mf, n_chips)
    art.update({
        "model_flops_total": mf,
        "compute_s": terms.compute_s, "memory_s": terms.memory_s,
        "collective_s": terms.collective_s, "dominant": terms.dominant,
        "useful_ratio": terms.useful_ratio,
        "roofline_fraction": terms.roofline_fraction,
    })
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    name = f"{arch}__{shape_name}__{mesh_kind}__{variant}"
    if tag:
        name += f"__{tag}"
    (out / f"{name}.json").write_text(json.dumps(art, indent=1))
    print(f"OK {name}: flops/chip={art['flops']:.3e} "
          f"bytes={art['bytes_accessed']:.3e} "
          f"wire={sum(coll.values()):.3e} peak={art['peak_bytes_est']/2**30:.2f}GiB "
          f"dominant={art['dominant']} frac={art['roofline_fraction']:.3f} "
          f"(lower {art['lower_s']}s compile {art['compile_s']}s)")
    return art


def run_pod_sync(arch: str, *, compress: bool, out_dir="results/dryrun"):
    """Quantify the sync-elision knob: compile the periodic cross-pod param
    sync as its own step and record its wire bytes. A train step under
    ``sync_period=k`` carries NO pod collectives; its amortized collective
    term is train_wire + sync_wire / k (EXPERIMENTS.md §Variants)."""
    from repro.dist.collectives import pod_sync_params
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=True)
    params_abs = api.abstract(cfg)
    params_sh = sharding.param_shardings(cfg, mesh)
    jitted = jax.jit(lambda p: pod_sync_params(p, mesh, compress=compress,
                                               pspecs=params_sh),
                     in_shardings=(params_sh,), out_shardings=params_sh)
    with jax.set_mesh(mesh):
        compiled = jitted.lower(params_abs).compile()
    coll = roofline.collective_bytes(compiled.as_text())
    art = {"arch": arch, "kind": "pod_sync", "compress": compress,
           "collectives": coll, "wire_bytes": sum(coll.values()),
           "collective_s": sum(coll.values()) / roofline.ICI_BW}
    name = f"{arch}__podsync__multipod__{'int8' if compress else 'precise'}"
    pathlib.Path(out_dir).mkdir(parents=True, exist_ok=True)
    (pathlib.Path(out_dir) / f"{name}.json").write_text(
        json.dumps(art, indent=1))
    print(f"OK {name}: wire={art['wire_bytes']:.3e} B "
          f"({art['collective_s']:.3f}s @ICI)")
    return art


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch")
    p.add_argument("--shape")
    p.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    p.add_argument("--variant", default="precise")
    p.add_argument("--policy", default=None)
    p.add_argument("--n-micro", type=int, default=1)
    p.add_argument("--remat", default="full")
    p.add_argument("--out", default="results/dryrun")
    p.add_argument("--tag", default="")
    p.add_argument("--probe3", action="store_true",
                   help="3-point loop probes (removes one-time fusion-break "
                        "bias; used for hillclimb cells)")
    p.add_argument("--decode2d", action="store_true",
                   help="weight-stationary decode: batch unsharded, weights "
                        "2D-sharded, cache sequence over all axes")
    p.add_argument("--all", action="store_true")
    p.add_argument("--pod-sync", action="store_true",
                   help="measure the cross-pod param-sync step instead")
    p.add_argument("--compress", action="store_true")
    args = p.parse_args()
    if args.pod_sync:
        run_pod_sync(args.arch, compress=args.compress, out_dir=args.out)
        return
    if args.decode2d:
        from jax.sharding import PartitionSpec as _P
        sharding.batch_pspec = lambda *a, **k: _P()

    if args.all:
        from repro.configs import ARCHS
        failures = []
        for arch in ARCHS:
            for shape_name in SHAPES:
                try:
                    run_cell(arch, shape_name, args.mesh, args.variant,
                             policy=args.policy, n_micro=args.n_micro,
                             remat=args.remat, out_dir=args.out, tag=args.tag)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((arch, shape_name, str(e)[:200]))
        if failures:
            print("FAILURES:", failures)
            raise SystemExit(1)
        return
    run_cell(args.arch, args.shape, args.mesh, args.variant,
             policy=args.policy, n_micro=args.n_micro, remat=args.remat,
             out_dir=args.out, tag=args.tag, probe3=args.probe3)


if __name__ == "__main__":
    raise SystemExit(main())
