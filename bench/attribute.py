"""One run of one cell, as ``bench/run.py`` makes it, with the serving
engine's layer spans on, and what ``run.py`` does not read yet: which
engine code and which program each idle gap and each device second belong
to.

    python3 bench/attribute.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The engine's span log (``repro.serve.spans``) is on from the engine's
build. A traced run keeps the spans of the profiled stretch and, from the
profiler's file, the device's program events (``XLA Modules``) and the
host's ``engine.step`` annotations; it adds five metrics to the result's
``metrics`` (``bench/metrics/<name>.py``, the ``ATTRIBUTION`` list below)
and, under ``attribution``: ``idle_causes`` (idle seconds by the innermost
engine span, else by the harness's label), ``clock`` (how far the mapped
``engine.step`` spans lie from their annotations), the window's programs
obtained by JAX's ``fun_name`` and the engine's program counters across
the window. An untraced run is ``run.py``'s with spans on: the two give
what the spans cost. Exit codes and the last line of standard output are
as for ``run.py``.
"""
import time

T_START = time.perf_counter()

import collections  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import attribution, client, main, trace  # noqa: E402

ATTRIBUTION = [
    dict(name="device.idle_obtain_share", unit="%"),
    dict(name="device.idle_engine_host_share", unit="%"),
    dict(name="step.prefill_device_share", unit="%"),
    dict(name="step.decode_device_us_per_token", unit="us"),
    dict(name="prefill.window_exe_misses", unit="count"),
]
COUNTERS = ("prefill_exe_misses", "programs_obtained")


class Attribution:
    """Turns the spans on and reads them, by standing in for four names of
    the harness for one run: ``main.load_spec`` (adds the metrics),
    ``client.counters`` (adds the engine's program counters),
    ``client.run`` (keeps the window and its counters) and ``main.Tracer``
    (keeps the spans and program events of the stretch)."""

    def __init__(self):
        import jax
        self.eng = None
        self.trace = None
        self.window = self.counters = None
        self.programs = []                 # (fun_name, start, end)
        jax.monitoring.register_event_duration_secs_listener(self._compiled)
        load_spec, counters, run_client = (main.load_spec, client.counters,
                                           client.run)
        att = self

        def with_metrics(*a, **kw):
            spec = load_spec(*a, **kw)
            spec["layer"] = spec["layer"] + ATTRIBUTION
            return spec

        def with_programs(eng):
            return dict(counters(eng),
                        **{k: eng.stats[k] for k in COUNTERS})

        def keep_window(*a, **kw):
            out = run_client(*a, **kw)
            att.window, att.counters = out.window, out.counters
            return out

        class SpanTracer(main.Tracer):
            def start(self):
                att.eng.spans.take()       # set-up's spans
                return super().start()

            def stop(self):
                super().stop()
                self.taken = att.eng.spans.take()

            def reduce(self, out, log):
                path = trace.find_xplane(str(self.dir))
                extra = attribution.extract(path) if path else {}
                t = super().reduce(out, log)
                t.update(extra, prog_spans=attribution.map_spans(
                    self.taken, t["off"]))
                att.trace = t
                return t

        main.load_spec, main.Tracer = with_metrics, SpanTracer
        client.counters, client.run = with_programs, keep_window

    def _compiled(self, event: str, secs: float, fun_name: str = "?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            now = time.perf_counter()
            self.programs.append((fun_name, now - secs, now))

    def hook(self, eng):
        self.eng = eng
        eng.spans.enable()

    def report(self, result: dict) -> dict:
        """``result`` with ``attribution`` put before its ``checks``."""
        w0, w1 = self.window
        got = collections.Counter(n for n, a, b in self.programs
                                  if w0 <= b <= w1)
        c = self.counters
        extra = dict(window_programs=dict(got.most_common()),
                     window_counters={k: c["end"][k] - c["start"][k]
                                      for k in COUNTERS})
        t = self.trace
        if t is not None:
            extra["idle_causes"] = attribution.idle_causes(
                t["busy"], t["lo"], t["hi"], t["prog_spans"], t["spans"])
            extra["idle_s"] = t["window_s"] - t["busy_s"]
            extra["clock"] = attribution.clock_check(
                t.get("annotations", []), t["prog_spans"])
            steps = sum(1 for n, *_ in t["prog_spans"] if n == "engine.step")
            extra["spans_per_step"] = len(t["prog_spans"]) / max(steps, 1)
            obt = [(b - a) * 1e-9 for n, a, b, *_ in t["prog_spans"]
                   if n == "program.obtain"]
            extra["obtains"] = dict(n=len(obt), s=sum(obt))
        checks = result.pop("checks")
        return dict(result, attribution=extra, checks=checks)


def run(argv, *, t_start: float, require_chip: bool = True) -> dict:
    att = Attribution()
    result = main.run(argv, t_start=t_start, require_chip=require_chip,
                      hook=att.hook)
    return att.report(result)


if __name__ == "__main__":
    try:
        res = run(sys.argv[1:], t_start=T_START)
    except main.Failure as e:
        print(f"error: {e}", file=sys.stderr)
        raise SystemExit(2)
    print(json.dumps(res))
