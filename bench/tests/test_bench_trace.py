"""The trace reduction: busy, idle and kernel time, and the labels of idle
gaps, on a small trace in the reduced form with hand-counted answers."""
import json
import pathlib

import pytest

from harness import trace as tr
from harness.main import BENCH, load_module

DATA = pathlib.Path(__file__).resolve().parent / "data"


def small():
    return json.loads((DATA / "trace_small.json").read_text())


def test_union_and_subtract():
    u = tr.union([(0, 10), (5, 12), (20, 30), (30, 31)])
    assert u == [(0, 12), (20, 31)]
    assert tr.subtract([(0, 40)], u) == [(12, 20), (31, 40)]
    assert tr.length(tr.clip(u, 6, 25)) == 6 + 5


def test_busy_idle_and_kernel_time_by_hand():
    t = small()
    ops, lo, hi = t["ops"], t["lo"], t["hi"]
    busy = tr.busy_intervals(ops, lo, hi)
    # by hand: the union of the ops' [start, start + dur) inside [lo, hi]
    want = tr.length(tr.clip(tr.union(
        [(s, s + d) for _, s, d in ops]), lo, hi))
    assert tr.length(busy) == want == t["expect"]["busy_ns"]
    secs, n = tr.kernel_seconds(ops, t["kernel_pattern"], lo, hi)
    assert n == t["expect"]["kernel_events"]
    assert secs == pytest.approx(t["expect"]["kernel_ns"] * 1e-9)


def test_idle_share_leaves_out_sleep():
    t = small()
    lo, hi = t["lo"], t["hi"]
    busy = tr.busy_intervals(t["ops"], lo, hi)
    ctx = dict(trace=dict(lo=lo, hi=hi, busy=busy, spans=t["spans"]))
    got = load_module(BENCH / "metrics" / "device.idle_share.py").read(ctx)
    sleep = tr.clip(tr.union([(a, b) for lab, a, b in t["spans"]
                              if lab == "sleep"]), lo, hi)
    span = (hi - lo) - tr.length(sleep)
    idle = span - tr.length(tr.subtract(busy, sleep))
    assert got == pytest.approx(100 * idle / span)
    assert got == pytest.approx(t["expect"]["idle_share_pct"])


def test_idle_gaps_are_labelled_by_harness_activity():
    t = small()
    busy = tr.busy_intervals(t["ops"], t["lo"], t["hi"])
    gaps = tr.idle_gaps(busy, t["lo"], t["hi"], t["spans"])
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert {g[0] for g in gaps} <= {"client", "engine.step", "sleep",
                                    "compile", "none"}
    assert gaps[0][0] == t["expect"]["longest_gap_label"]


def test_top_ops_group_instances():
    ops = [["fusion.1", 0, 10], ["fusion.22", 20, 5], ["copy.3", 40, 7]]
    got = tr.top_ops(ops, 0, 100)
    assert [k for k, _ in got] == ["fusion", "copy"]
    assert [v for _, v in got] == pytest.approx([15e-9, 7e-9])
