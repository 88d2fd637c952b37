"""One run of one cell: build, warm up, drive the window, read the metrics,
check the served tokens against the plain reference, print the result.

Everything that belongs to a configuration, a traffic mix or a metric is a
file found by its name in ``BENCHMARK.json``: ``bench/configs/<file>`` (with
the plain reference it names beside it), ``bench/traffic/<traffic>.json``
and ``bench/metrics/<metric>.py`` (a ``read(ctx)`` that returns a number, or
None when there is nothing to read).
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import pathlib
import sys
import time
from typing import Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
TRACE_S = 12.0            # longest traced stretch, in the window's middle
SAMPLE_TOKENS = 320       # served tokens the reference checks per run
SAMPLE_MAX = 10           # requests at most in that sample


class Failure(Exception):
    """A run that cannot produce a result (exit code 2, no result line)."""


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec(workload: str, *, config: Optional[str] = None,
              traffic: Optional[str] = None) -> dict:
    """The cell, its configuration and traffic files, and its metrics."""
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise Failure(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg_path = pathlib.Path(config) if config else CHECKOUT / conf["file"]
    mix_path = pathlib.Path(traffic) if traffic else \
        BENCH / "traffic" / f"{cell['traffic']}.json"
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return dict(cell=cell, cfg=json.loads(cfg_path.read_text()),
                mix=json.loads(mix_path.read_text()), e2e=e2e, layer=layer)


def parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="Run one benchmark cell.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", default="", choices=("", "int8"),
                   help="serve on the program's lower-precision rung (the "
                        "control that the comparison must fail)")
    p.add_argument("--config", default=None,
                   help="configuration file in place of the cell's")
    p.add_argument("--traffic", default=None,
                   help="traffic file in place of the cell's (rate sweeps)")
    return p


class CompileLog:
    """JAX's own events, stamped on the host clock: 'compile' spans every
    program obtained (compiled, or loaded from the persistent cache), and a
    'cache_load' mark for each of those that was a load."""

    def __init__(self):
        import jax
        self.events: List[tuple] = []          # (kind, start, end)
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            now = time.perf_counter()
            self.events.append(("compile", now - secs, now))

    def _ev(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            now = time.perf_counter()
            self.events.append(("cache_load", now, now))

    def between(self, a: float, b: float) -> List[tuple]:
        return [e for e in self.events if a <= e[2] <= b]


def check_device(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise Failure(f"no TPU attached (JAX found {devs[0].platform})")
        if len(devs) < chips:
            raise Failure(f"{chips} chips needed, {len(devs)} attached")
    return devs


def peaks_for(kind: str, require: bool) -> Optional[dict]:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind in table:
        return table[kind]
    if require:
        raise Failure(f"device kind {kind!r} is not in bench/peaks.json")
    return None


def sample_for_check(records, seed: int) -> list:
    """Finished window requests for the comparison, drawn from the seed:
    the longest first, then others until SAMPLE_TOKENS served tokens."""
    import numpy as np
    done = [r for r in records if r.counted and r.done and r.seen >= 1]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.seen, r.uid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 0xC0FFEE]).permutation(len(rest))
    pick, toks = [longest], longest.seen
    for i in order:
        if toks >= SAMPLE_TOKENS or len(pick) >= SAMPLE_MAX:
            break
        pick.append(rest[i])
        toks += rest[i].seen
    return pick


def compare(ref, params, cfg: dict, picked) -> Dict[str, float]:
    """Gaps by which served tokens' reference logits lie below the
    reference's best, over every served token of the sample: the mean
    (``logit_gap_mean``, the number compared: steady from seed to seed) and
    the widest (``logit_gap_max``, a maximum over near-ties that swings with
    the seed), with the share of tokens equal to the reference's argmax."""
    import numpy as np
    widest, total, agree, n = 0.0, 0.0, 0, 0
    for rec in picked:
        prompt, served = rec.req.prompt, rec.req.out
        toks = np.asarray(prompt + served[:-1], np.int32)
        logits = ref.logits_at(params, toks, len(prompt) - 1, cfg)
        best = logits.max(-1)
        got = logits[np.arange(len(served)), np.asarray(served)]
        gap = best - got
        widest = max(widest, float(gap.max()))
        total += float(gap.sum())
        agree += int((gap == 0).sum())
        n += len(served)
    return dict(logit_gap_max=widest, logit_gap_mean=total / max(n, 1),
                top1_agree=agree / max(n, 1), tokens=n,
                requests=len(picked))


def setup(args, *, require_chip: bool = True, hook=None):
    """Everything before the window, for a run and for the knee sweep alike:
    the device check, the persistent cache, weights from the seed, the
    engine, the admission tails primed and every shape warmed. Returns a
    namespace with ``spec``, ``cfg``, ``mix``, ``devs``, ``peaks``, ``ref``,
    ``params``, ``eng``, ``log`` and ``primed``. ``hook(engine)`` runs after
    the engine is built (tests plant faults there)."""
    import types
    spec = load_spec(args.workload, config=args.config, traffic=args.traffic)
    cell, cfg, mix = spec["cell"], spec["cfg"], spec["mix"]
    devs = check_device(cell["chips"], require_chip)
    sys.path.insert(0, str(CHECKOUT / "src"))
    import jax.numpy as jnp
    from harness import system, traffic, weights
    cache_dir = system.compile_cache_dir(CHECKOUT)
    log = CompileLog()
    peaks = peaks_for(devs[0].device_kind, require_chip)
    ref = load_module(BENCH / "configs" / f"{cfg['reference']}.py")
    from repro.models import api

    layout = ref.param_layout(cfg)
    pcfg = system.program_config(cfg)
    weights.check_against(layout, api.abstract(pcfg, jnp.bfloat16))
    params = weights.make(layout, args.seed)
    eng_spec = cfg["engine"]
    if traffic.max_request_tokens(mix) > eng_spec["max_len"]:
        raise Failure("the traffic's longest request exceeds max_len")
    eng = system.build_engine(pcfg, params, eng_spec, args.seed,
                              system.knobs_for(args.control))
    if hook is not None:
        hook(eng)
    primed = 0
    if not args.control:
        primed = system.prime_admission_tails(
            eng, system.prime_marker(cache_dir, cfg), log)
    system.warm_megasteps(eng)
    system.warm_traffic(eng, cfg["vocab_size"], args.seed)
    print(f"set-up: primed {primed} admission programs; "
          f"{len(log.events)} compile events so far", file=sys.stderr)
    return types.SimpleNamespace(spec=spec, cfg=cfg, mix=mix, devs=devs,
                                 peaks=peaks, ref=ref, params=params,
                                 eng=eng, log=log, primed=primed)


def run(argv=None, *, t_start: float, require_chip: bool = True,
        hook=None) -> dict:
    """Run one cell; returns the result dictionary."""
    args = parser().parse_args(argv)
    su = setup(args, require_chip=require_chip, hook=hook)
    spec, cfg, mix, eng, log = su.spec, su.cfg, su.mix, su.eng, su.log
    cell, devs, peaks, ref, params = (spec["cell"], su.devs, su.peaks,
                                      su.ref, su.params)
    eng_spec = cfg["engine"]
    dev = devs[0]
    from harness import client, traffic
    jobs = traffic.generate(mix, args.seed, args.seconds, cfg["vocab_size"])
    del su                     # the engine is freed before the reference

    tracer = Tracer(args.seconds) if args.trace else None
    out = client.run(eng, jobs, mix, args.seconds,
                     marks=tracer.marks() if tracer else ())
    setup_s = out.t0 - t_start
    mem = dev.memory_stats() or {}
    trace = tracer.reduce(out, log) if tracer else None

    counted = [r for r in out.records if r.counted]
    failed = sum(1 for r in counted if not r.done)
    ctx = dict(cfg=cfg, mix=mix, cell=cell, seconds=args.seconds,
               out=out, counted=counted, setup_s=setup_s, mem=mem,
               peaks=peaks, trace=trace, compiles=log,
               page_size=eng_spec["page_size"], n_pages=eng.pool.spec.usable,
               flops=load_module(BENCH / "harness" / "flops.py"))
    metrics_spec = spec["layer"] if args.trace else spec["e2e"]
    metrics = {}
    for m in metrics_spec:
        v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    n_tt = sum(1 for r in counted if r.first_seen is not None)
    print(f"window: {len(counted)} requests due, {n_tt} with a first token, "
          f"{failed} failed; drain {out.drain_s:.1f} s; {out.steps} steps",
          file=sys.stderr)
    diagnose(out, log)

    # ---- the comparison, once the program's state is freed
    picked = sample_for_check(out.records, args.seed)
    keep = {id(r) for r in picked}
    for r in out.records:
        if id(r) not in keep:
            r.req = None
    del eng
    gc.collect()
    t_ref = time.perf_counter()
    got = compare(ref, params, cfg, picked) if picked else {}
    checks = {k: {"value": got.get(k), "limit": lim}
              for k, lim in cfg["limits"].items()}
    correct = bool(picked) and all(
        c["value"] is not None and c["limit"] is not None
        and c["value"] <= c["limit"] for c in checks.values())
    print(f"reference: {got} in {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    result = dict(correct=correct, attempted=len(counted), failed=failed,
                  metrics=metrics,
                  device=dict(platform=dev.platform, kind=dev.device_kind,
                              count=len(devs),
                              memory_peak_bytes=int(mem.get(
                                  "peak_bytes_in_use", 0))))
    if trace is not None:
        result["device"].update(busy_s=trace["busy_s"],
                                window_s=trace["window_s"])
        result["breakdown"] = dict(device_ops=trace["top_ops"],
                                   idle_gaps=trace["idle_gaps"])
    result["checks"] = checks
    return result


def diagnose(out, log) -> None:
    """Where the window's host time went (standard error only)."""
    import resource
    import numpy as np
    w0, w1 = out.window
    steps = np.array([b - a for lab, a, b in out.spans
                      if lab == "engine.step" and w0 <= a < w1])
    comp = log.between(w0, w1)
    got = sum(1 for e in comp if e[0] == "compile")
    hits = sum(1 for e in comp if e[0] == "cache_load")
    busy = sum(b - a for lab, a, b in out.spans if w0 <= a < w1
               and lab != "sleep")
    q = np.percentile(steps, [50, 95, 100]) if steps.size else [0, 0, 0]
    print(f"host: {steps.size} steps in the window, step s p50 {q[0]:.4f} "
          f"p95 {q[1]:.4f} max {q[2]:.3f}, not asleep {busy:.1f} s; "
          f"window programs obtained {got} ({hits} cache loads), s "
          f"{sum(e[2] - e[1] for e in comp):.1f}; peak host RSS "
          f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.1f}"
          f" GiB", file=sys.stderr)


class Tracer:
    """Profiles the middle of the window (TRACE_S at most) and reduces the
    trace once the run is over."""

    def __init__(self, seconds: float):
        self.span = min(seconds, TRACE_S)
        self.off = (seconds - self.span) / 2
        self.dir = CHECKOUT / ".bench_cache" / "trace"
        self.t = {}

    def marks(self):
        return [(self.off, self.start)]

    def start(self):
        import shutil
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # Python calls would flood the host
        opts.host_tracer_level = 1        # user annotations (the anchor)
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        from harness.trace import ANCHOR
        self.t["host0"] = time.perf_counter()
        with jax.profiler.TraceAnnotation(ANCHOR):
            self.t["anchor"] = time.perf_counter()
        return self.span, self.stop       # stop a full span after this

    def stop(self):
        import jax
        self.t["host1"] = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self, out, log) -> dict:
        import shutil
        from harness import trace as tr
        path = tr.find_xplane(str(self.dir))
        if path is None:
            raise Failure("the profiler wrote no trace")
        ev = tr.extract(path)
        shutil.rmtree(self.dir, ignore_errors=True)
        if ev["anchor_ns"] is None or not ev["ops"]:
            raise Failure(f"trace without anchor or device ops: lines "
                          f"{ev['lines']}")
        off = ev["anchor_ns"] - self.t["anchor"] * 1e9   # host s -> trace ns
        lo, hi = self.t["host0"] * 1e9 + off, self.t["host1"] * 1e9 + off
        busy = tr.busy_intervals(ev["ops"], lo, hi)
        spans = [(lab, a * 1e9 + off, b * 1e9 + off)
                 for lab, a, b in out.spans + log.events]
        window_s = (hi - lo) * 1e-9
        return dict(ops=ev["ops"], lo=lo, hi=hi, off=off, busy=busy,
                    busy_s=tr.length(busy) * 1e-9, window_s=window_s,
                    host_window=(self.t["host0"], self.t["host1"]),
                    spans=spans,
                    top_ops=tr.top_ops(ev["ops"], lo, hi),
                    idle_gaps=tr.idle_gaps(busy, lo, hi, spans),
                    lines=ev["lines"])


def main(argv=None, *, t_start: float) -> int:
    try:
        result = run(argv, t_start=t_start)
    except Failure as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0
