"""On-chip smoke test of the serving path, through the CLI's own builder.

    python3 chip_smoke.py                # one TPU chip
    python3 chip_smoke.py --four-chips   # the sharded path on a 4-chip host

One chip: ``launch.serve.serve`` builds and drives exactly what
``python -m repro.launch.serve`` would: phi4-mini-3.8b at its published
widths and all 32 layers, bf16 params and pages, 8 requests on 8 slots,
``--megastep 8`` at page size 16. Partway through, the engine is
forced down to the int8 rung (int8 matmuls + int8 KV pages). Checks:

* every request finishes; the attention audit saw the fused paged kernel
  (``kernel_single`` > 0) and never the gather reference
  (``gather_single`` == 0); the int8 matmul ran as the Pallas kernel and no
  reference branch of ``kernels.ops`` was taken;
* the int8 matmul kernel matches its jnp reference at phi4 widths;
* one decode step's logits, with the kernel and with ``use_kernel=False``,
  agree on the engine's own live state, for the precise and the int8 rung.

Four chips: the same requests served by a paged engine on a (data=4,
model=1) mesh, which runs the shard_map'd decode kernel and the ring
prefill, must give the same greedy tokens as one chip; the mesh run also
revokes and restores two devices (elastic re-home). This comparison runs
in float32 with ``highest`` matmul precision at 8 of the 32 layers
(float32 at full depth does not fit one chip): ring and single-device
prefill reduce in different orders, and in bf16 that noise flips
near-tied argmaxes of random-weight logits.

Every phase must pass; the script exits nonzero otherwise and prints the
result JSON only as its last line on success. It needs a TPU: with none
attached it exits 1 without a result.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "phi4-mini-3.8b"
SLOTS, REQUESTS, PAGE, MEGASTEP = 8, 8, 16, 8
MAX_LEN, PROMPT, CHUNK, MAX_NEW = 2048, 512, 128, 64
# relative RMS distance allowed between kernel and reference logits. The
# two paths differ only in the new token's attention: the kernel keeps the
# softmax weights and the PV product in f32, the reference rounds them to
# bf16 (relative step 2**-8), and each layer's residual update carries that
# noise on. On the CPU backend (phi4 at reduced widths, kernel interpreted)
# it measured 0.8% of the logits' RMS at 2 layers and 1.6-2.0% at 32, while
# a deliberately wrong attention (KV heads rolled, one page's positions
# shifted, the newest page masked) moved the logits by 22-139%. 6% sits
# three times above the noise and four times below the smallest fault.
LOGITS_RTOL = 6e-2
INT8_RTOL = 1e-2      # bf16 rounding of the output on exact int32 sums

failures: list = []


def check(ok: bool, what: str) -> None:
    print(f"{'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        failures.append(what)


class CompileLog:
    """Counts XLA backend compiles and their seconds (JAX's own events)."""

    def __init__(self):
        import jax
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.s += secs


@contextlib.contextmanager
def outside_dispatch_audit():
    """Keep a comparison's own traces out of the engine's dispatch audit."""
    from repro.kernels import ops as kops
    from repro.models import attention as attn
    saved = [(c, collections.Counter(c)) for c in (attn.DISPATCH_COUNTS,
                                                   kops.DISPATCH_COUNTS)]
    try:
        yield
    finally:
        for c, old in saved:
            c.clear()
            c.update(old)


def rel_rms(a, b) -> float:
    import numpy as np
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def serve_argv(extra=()) -> list:
    return ["--arch", ARCH, "--megastep", str(MEGASTEP),
            "--page-size", str(PAGE), "--slots", str(SLOTS),
            "--requests", str(REQUESTS), "--max-len", str(MAX_LEN),
            "--prompt-len", str(PROMPT), "--prefill-chunk", str(CHUNK),
            "--max-new", str(MAX_NEW), "--seed", "0", *extra]


def int8_matmul_parity() -> None:
    """The W8A8 kernel against its jnp reference at phi4's MLP widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops as kops, ref
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (SLOTS, 3072), jnp.bfloat16)
    w = (jax.random.normal(kw, (3072, 8192), jnp.float32)
         / np.sqrt(3072)).astype(jnp.bfloat16)
    with outside_dispatch_audit():
        got = np.asarray(jax.jit(kops.quantized_matmul)(x, w), np.float32)
        want = np.asarray(jax.jit(ref.quantized_matmul_ref)(x, w),
                          np.float32)
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    check(err <= INT8_RTOL, f"int8_matmul kernel vs reference at 8x3072x8192:"
                            f" max|d|/max|ref| = {err:.2e} <= {INT8_RTOL}")


def decode_logits_parity(eng, label: str) -> None:
    """One decode step of the engine's live state, fused kernel vs the
    gather reference (``use_kernel=False``), same params and pages."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.train import step as step_mod
    eng._drain_pipeline()       # land in-flight tokens; caches settle
    rows = [i for i, r in enumerate(eng.slots) if r is not None]
    act = jnp.asarray(np.array([r is not None for r in eng.slots]))
    args = (eng.params, jnp.asarray(eng.cur_tokens)[:, None],
            jnp.asarray(eng.positions), act, eng.caches)
    out = {}
    with outside_dispatch_audit():
        for use_kernel in (True, False):
            step = jax.jit(step_mod.make_paged_serve_step(
                eng.cfg, eng.active_knobs, use_kernel=use_kernel,
                dynamic_scatter=True))
            logits, new_caches = step(*args)
            del new_caches
            out[use_kernel] = np.asarray(logits, np.float32).reshape(
                len(eng.slots), -1)[rows]
    err = rel_rms(out[True], out[False])
    top1 = float(np.mean(out[True].argmax(-1) == out[False].argmax(-1)))
    check(bool(np.isfinite(out[True]).all()),
          f"{label}: kernel logits finite, shape {out[True].shape}")
    check(err <= LOGITS_RTOL,
          f"{label}: decode logits kernel vs use_kernel=False at positions "
          f"{[int(eng.positions[i]) for i in rows]}: rel RMS {err:.2e} <= "
          f"{LOGITS_RTOL} (top-1 agreement {top1:.3f})")


def one_chip(log: CompileLog) -> None:
    import numpy as np
    from repro.kernels import ops as kops
    from repro.launch.serve import serve
    from repro.models import attention as attn

    int8_matmul_parity()
    state = {"swap_step": None, "precise_checked": False,
             "int8_checked": False, "rung": None}

    def on_step(eng, step):
        live = [r for r in eng.slots if r is not None]
        if state["rung"] is None:
            state["rung"] = next(
                (i for i, v in enumerate(eng.table.variants)
                 if v.knobs.matmul_precision == "int8" and v.knobs.kv_quant),
                None)
            if state["rung"] is None:
                raise RuntimeError("no int8 matmul + int8 KV rung in the "
                                   f"ladder {[v.name for v in eng.table.variants]}")
        if not state["precise_checked"] and len(live) == SLOTS:
            decode_logits_parity(eng, "precise rung")
            state["precise_checked"] = True
            eng.request_variant(state["rung"])
            state["swap_step"] = step
            print(f"forced swap to {eng.table.variants[state['rung']].name}"
                  f" at step {step}", flush=True)
        elif (state["swap_step"] is not None and not state["int8_checked"]
              and eng.active_variant == state["rung"]
              and step >= state["swap_step"] + 2 and live):
            decode_logits_parity(eng, "int8 rung")
            state["int8_checked"] = True

    t0 = time.perf_counter()
    served = serve(serve_argv(), on_step=on_step)
    wall = time.perf_counter() - t0
    eng, reqs, summary = served.engine, served.requests, served.summary
    print(f"serve: {summary['done']}/{summary['requests']} done, "
          f"{summary['tokens']} tokens, {summary['steps']} engine steps, "
          f"{wall:.1f}s including compiles", flush=True)
    check(summary["done"] == REQUESTS and not summary["unfinished"],
          "every request done")
    check(all(len(r.out) == MAX_NEW for r in reqs),
          f"every request produced {MAX_NEW} tokens")
    check(state["precise_checked"] and state["int8_checked"],
          "logits compared on both rungs")
    rung = state["rung"]
    check(any(i == rung for _, i in eng.swaps),
          f"swap to the int8 rung applied: swaps={eng.swaps}")
    kp = eng.caches[0].kp
    check(str(kp.dtype) == "int8", f"pages after the swap are {kp.dtype}")
    a, k = attn.DISPATCH_COUNTS, kops.DISPATCH_COUNTS
    print(f"dispatch audit: attention={dict(a)} ops={dict(k)}")
    check(a["kernel_single"] > 0 and a["gather_single"] == 0,
          "paged decode traced only through the fused kernel")
    check(k["int8_matmul"] > 0, "int8 rung traced the int8 matmul kernel")
    check(k["int8_matmul_ref"] == 0 and k["ssd_ref"] == 0,
          "no reference branch of kernels.ops taken")
    mega = sorted(eng._megasteps)
    pre = sorted(key[1] for key in eng._prefills)
    print(f"executables: megastep (variant, k)={mega} "
          f"prefill chunk lengths={pre}; XLA compiles={log.n} "
          f"compile_s={log.s:.1f}", flush=True)
    check(bool(np.isfinite(summary["token_latency_s"][50])),
          "token latencies recorded")


def four_chips(log: CompileLog) -> None:
    import jax
    from repro.launch.serve import serve
    from repro.models import attention as attn
    jax.config.update("jax_default_matmul_precision", "highest")
    extra = ["--dtype", "float32", "--layers", "8", "--max-new", "24"]

    single = serve(serve_argv(extra))
    want = {r.uid: list(r.out) for r in single.requests}
    check(single.summary["done"] == REQUESTS, "one chip: every request done")
    # free device 0 before the mesh engine places its replicas
    del single
    gc.collect()
    attn.DISPATCH_COUNTS.clear()

    mesh = serve(serve_argv(extra + ["--mesh", "4x1",
                                     "--chaos", "revoke@6+3:2,restore@14"]))
    eng = mesh.engine
    got = {r.uid: list(r.out) for r in mesh.requests}
    check(mesh.summary["done"] == REQUESTS, "4 chips: every request done")
    same = sum(got[u] == want[u] for u in want)
    check(same == len(want), f"greedy tokens equal one chip's for "
                             f"{same}/{len(want)} requests")
    a = attn.DISPATCH_COUNTS
    print(f"dispatch audit: attention={dict(a)}")
    check(a["kernel_sharded"] > 0 and a["gather_mesh"] == 0,
          "decode traced only through the shard_map'd kernel")
    check(a["ring_prefill"] > 0 and a["prefill_gather_mesh"] == 0,
          "prefill traced only through the ring")
    kinds = [e.get("kind") for e in eng.elastic_log]
    print(f"elastic log kinds: {kinds}; rehomes={eng.stats['rehomes']}")
    check(eng.stats["rehomes"] >= 2, "revoke and restore both re-homed")
    print(f"XLA compiles={log.n} compile_s={log.s:.1f}", flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the sharded path and its one-chip "
                        "comparison (needs a host with 4 chips)")
    args = p.parse_args(argv)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"error: no TPU attached (JAX found {devs[0].platform})",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"error: {need} chips needed, {len(devs)} attached",
              file=sys.stderr)
        return 1
    from repro import roofline
    from repro.launch.compile_cache import use_compile_cache
    peaks = roofline.peaks(devs[0].device_kind)   # unknown chip: error
    print(f"device: {devs[0].device_kind} x{len(devs)}; peaks "
          f"{peaks.bf16_flops / 1e12:.0f} TFLOP/s bf16, "
          f"{peaks.hbm_bw / 1e9:.0f} GB/s HBM ({peaks.source})")
    print(f"compile cache: {use_compile_cache()}")
    log = CompileLog()
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(log)
    except Exception as e:  # noqa: BLE001 - report, then fail the run
        import traceback
        traceback.print_exc()
        failures.append(f"{type(e).__name__}: {e}")
    print(f"total {time.perf_counter() - t0:.1f}s")
    if failures:
        print(f"chip smoke FAILED: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
