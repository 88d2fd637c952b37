"""Engine spans and program events on the trace: idle time by the engine
code that was running, device time by program, on a small hand-made
stretch with hand-counted answers; and the attribution run end to end at
a test size, without a chip."""
import json
import os
import subprocess
import sys
import types

import pytest

from harness import attribution
from harness import trace as tr
from harness.main import BENCH, load_module

DATA = BENCH / "tests" / "data"
NEW = ("device.idle_obtain_share", "device.idle_engine_host_share",
       "step.prefill_device_share", "step.decode_device_us_per_token",
       "prefill.window_exe_misses")


def small():
    t = json.loads((DATA / "trace_spans_small.json").read_text())
    t["busy"] = tr.busy_intervals(t["ops"], t["lo"], t["hi"])
    return t


def ctx_of(t):
    out = types.SimpleNamespace(counters=t["counters"])
    return dict(trace=t, out=out)


def read(name, ctx):
    return load_module(BENCH / "metrics" / f"{name}.py").read(ctx)


def test_idle_causes_by_hand_and_sum_to_the_idle_time():
    t = small()
    got = attribution.idle_causes(t["busy"], t["lo"], t["hi"],
                                  t["prog_spans"], t["spans"])
    want = t["expect"]["idle_causes_ns"]
    assert set(got) == set(want)
    for k, ns in want.items():
        assert got[k] == pytest.approx(ns * 1e-9), k
    idle = (t["hi"] - t["lo"]) - tr.length(t["busy"])
    assert idle == t["expect"]["idle_ns"]
    assert sum(got.values()) == pytest.approx(idle * 1e-9)
    assert list(got.values()) == sorted(got.values(), reverse=True)


@pytest.mark.parametrize("name", NEW + ("device.idle_share",))
def test_metric_by_hand(name):
    assert read(name, ctx_of(small())) == pytest.approx(
        small()["expect"][name])


def test_idle_parts_lie_within_the_idle_share():
    ctx = ctx_of(small())
    parts = (read("device.idle_obtain_share", ctx)
             + read("device.idle_engine_host_share", ctx))
    assert parts <= read("device.idle_share", ctx)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_gives_none(name):
    """An untraced run, and a trace or counters without the engine's
    spans, programs or counters (a program without them), read None."""
    assert read(name, dict(trace=None, out=types.SimpleNamespace(
        counters={"start": {}, "end": {}}))) is None
    t = small()
    for key in ("prog_spans", "modules"):
        del t[key]
    t["counters"] = {"start": {"row_tokens": 1}, "end": {"row_tokens": 2}}
    assert read(name, ctx_of(t)) is None


def test_programs_under_other_names_read_none():
    t = small()
    t["modules"] = [["jit_step", s, d] for _, s, d in t["modules"]]
    for name in ("step.prefill_device_share",
                 "step.decode_device_us_per_token"):
        assert read(name, ctx_of(t)) is None


def test_clock_check_by_hand():
    t = small()
    got = attribution.clock_check(t["annotations"], t["prog_spans"])
    want = t["expect"]["clock"]
    assert got["spans"] == want["spans"]
    assert got["median_us"] == pytest.approx(want["median_us"])
    assert got["max_us"] == pytest.approx(want["max_us"])


def test_map_spans_moves_host_seconds_onto_the_trace_clock():
    from repro.serve.spans import Span
    got = attribution.map_spans([Span(3, "decode.drain", 2.0, 2.5, 1,
                                      {"tokens": 4})], off=-1.5e9)
    assert got == [["decode.drain", 0.5e9, 1.0e9, 3, 1, {"tokens": 4}]]


def test_attribution_run_without_a_chip():
    """The entry point's wiring at a test size: the engine's spans are on,
    its counters reach the window's snapshots, and the window's programs
    are counted by name (an untraced run: no trace metrics)."""
    code = (
        "import json, sys, time; sys.path.insert(0, 'bench');"
        "import attribute;"
        "r = attribute.run(sys.argv[1:], t_start=time.perf_counter(),"
        " require_chip=False); print(json.dumps(r))")
    args = ["--workload", "phi4.short_unshared", "--seed", "2147483990",
            "--seconds", "3", "--trace", "0",
            "--config", str(DATA / "tiny_dense.json"),
            "--traffic", str(DATA / "tiny_chat.json")]
    p = subprocess.run([sys.executable, "-c", code, *args],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       cwd=BENCH.parent)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"], r["checks"]
    assert list(r)[-2:] == ["attribution", "checks"]
    assert "output_tok_s" in r["metrics"]
    att = r["attribution"]
    assert all(isinstance(v, int) and v > 0
               for v in att["window_programs"].values())
    c = att["window_counters"]
    assert 0 <= c["prefill_exe_misses"] <= c["programs_obtained"]
