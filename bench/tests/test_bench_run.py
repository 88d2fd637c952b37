"""The harness end to end at a test size, without a chip: a sound run is
correct; the program's lower-precision rung (the control) and planted
faults in the timed path are not; and with no TPU the real entry point
exits nonzero without a result."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
CELL = "phi4.short_unshared"


def drive(fault, *args, config="tiny_dense", traffic="tiny_chat", seed=5):
    cmd = [sys.executable, str(BENCH / "tests" / "drive_tiny.py"), fault,
           "--workload", CELL, "--seed", str(seed),
           "--seconds", "3", "--config", str(DATA / f"{config}.json"),
           "--traffic", str(DATA / f"{traffic}.json"), *args]
    p = subprocess.run(cmd, capture_output=True, text=True, env=ENV,
                       timeout=600, cwd=BENCH.parent)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("config,traffic", [("tiny_dense", "tiny_chat"),
                                            ("tiny_ssd", "tiny_closed")])
def test_sound_run_is_correct(config, traffic):
    r = drive("none", config=config, traffic=traffic)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    assert set(r["metrics"]) == {
        m["name"] for m in bench["end_to_end"]
        if CELL in m.get("workloads", [CELL])}
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", ["token", "state"])
def test_planted_fault_is_not_correct(fault):
    r = drive(fault)
    assert not r["correct"], r["checks"]


def test_control_rung_is_not_correct():
    r = drive("none", "--control", "int8")
    assert not r["correct"], r["checks"]


def test_no_tpu_exits_nonzero_without_result(tmp_path):
    cmd = [sys.executable, "bench/run.py", "--workload", CELL,
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, capture_output=True, text=True, env=ENV,
                       timeout=300, cwd=BENCH.parent)
    assert p.returncode != 0
    assert not p.stdout.strip()
    # a directory with only BENCHMARK.json and the benchmark's files
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(cmd, capture_output=True, text=True, env=ENV,
                       timeout=300, cwd=tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_priming_trusts_the_cache_not_the_marker(tmp_path):
    """A marker from an earlier run stands only while the probed tails all
    load from the cache; a probe that compiles primes every tail again."""
    from harness import system

    class Log:
        events = []

    class Eng:
        prefill_chunk = 8
        params = caches = None
        cached = set()

        def _prefill_exe(self, c):
            eng = self

            class Exe:
                def lower(self, *a):
                    return self

                def compile(self):
                    Log.events.append(("compile", 0, 0))
                    if c in eng.cached:
                        Log.events.append(("cache_load", 0, 0))
                    eng.cached.add(c)
            return Exe()

    marker = tmp_path / "primed" / "cfg"
    eng = Eng()
    assert system.prime_admission_tails(eng, marker, Log) == 8
    assert marker.exists()
    assert system.prime_admission_tails(eng, marker, Log) == 0
    eng.cached.discard(1)                 # the cache lost a primed tail
    assert system.prime_admission_tails(eng, marker, Log) == 8
