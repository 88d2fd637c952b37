"""Device trace: capture with JAX's profiler, reduce to the events the
metrics need, and the arithmetic on them (busy time, idle gaps, kernel time).

``extract`` turns a profiler ``.xplane.pb`` into a plain dictionary:
device-op events of the first device plane as ``[name, start_ns, dur_ns]``
and the harness's anchor span, so that host and device clocks can be lined
up. Everything after that works on the dictionary, which is what the tests
check on a small recorded trace.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

ANCHOR = "bench.anchor"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# the line of a TPU device plane that holds one event per executed HLO op;
# an event's name is the op's HLO text ("%fusion.12 = bf16[...] fusion(...)")
OP_LINES = ("XLA Ops",)


def extract(path: str) -> dict:
    """Reduce an xplane file to device ops and the anchor's host time."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: List[list] = []
    anchor = None
    dev_planes = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev_planes.append(plane)
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ANCHOR and anchor is None:
                    anchor = ev.start_ns
    dev_planes.sort(key=lambda p: p.name)
    lines = []
    if dev_planes:
        plane = dev_planes[0]
        for line in plane.lines:
            lines.append(line.name)
            if line.name in OP_LINES:
                for ev in line.events:
                    ops.append([ev.name, ev.start_ns, ev.duration_ns])
    return dict(device=dev_planes[0].name if dev_planes else None,
                n_device_planes=len(dev_planes), lines=lines,
                ops=ops, anchor_ns=anchor)


def find_xplane(root: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)
    return hits[-1] if hits else None


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                    float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base, minus):
    """Parts of the (merged) ``base`` intervals not covered by ``minus``."""
    out = []
    minus = union(minus)
    for a, b in base:
        cur = a
        for c, d in minus:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
        if cur < b:
            out.append((cur, b))
    return out


def busy_intervals(ops, lo_ns: float, hi_ns: float):
    """Union of device-op intervals inside [lo, hi] (ns)."""
    return clip(union([(s, s + d) for _, s, d in ops]), lo_ns, hi_ns)


_SUFFIX = re.compile(r"[.:]\d+$")


def op_family(name: str) -> str:
    """An op's name without its instance number or HLO text
    ('%fusion.12 = bf16[8] fusion(...)' -> 'fusion'); Pallas kernels, which
    XLA runs as custom calls, are marked '[tpu_custom_call]'."""
    fam = _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))
    return fam + "[tpu_custom_call]" if "tpu_custom_call" in name else fam


# ops whose event spans the ops of their body (counted there instead)
CONTAINERS = ("while", "conditional", "call")


def top_ops(ops, lo_ns, hi_ns, n: int = 10):
    """The device operations (by family) that took most time, in seconds;
    control-flow ops, whose events enclose their bodies' ops, are left
    out."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for name, s, d in ops:
        if op_family(name) in CONTAINERS:
            continue
        a, b = max(s, lo_ns), min(s + d, hi_ns)
        if b > a:
            tot[op_family(name)] += (b - a) * 1e-9
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:n]


def kernel_seconds(ops, pattern: str, lo_ns, hi_ns) -> Tuple[float, int]:
    """Device seconds and event count of ops whose name matches
    ``pattern`` (a regular expression)."""
    rx = re.compile(pattern)
    t, k = 0.0, 0
    for name, s, d in ops:
        a, b = max(s, lo_ns), min(s + d, hi_ns)
        if b > a and rx.search(name):
            t += (b - a) * 1e-9
            k += 1
    return t, k


def idle_gaps(busy, lo: float, hi: float, spans, n: int = 10):
    """The longest idle gaps in [lo, hi] (ns), each labelled 'compile' where
    it overlaps a compile or cache load, else by the harness activity that
    overlapped it most ('client', 'engine.step', 'sleep'); returned in
    seconds."""
    gaps = subtract([(lo, hi)], busy)
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        ov: Dict[str, float] = collections.defaultdict(float)
        for label, s, e in spans:
            x, y = max(a, s), min(b, e)
            if y > x:
                ov[label] += y - x
        if ov.get("compile"):
            label = "compile"
        else:
            label = max(ov, key=ov.get) if ov else "none"
        out.append([label, (b - a) * 1e-9])
    return out
