"""The engine's own spans and the device's named programs on the trace's
clock: which engine code and which program each idle gap and each device
second belong to.

``extract`` reads what ``trace.extract`` leaves out of a profiler file: the
device plane's ``XLA Modules`` line (one event per executed program, named
after the jitted function: ``jit_prefill_chunk``, ``jit_decode_megastep``)
and the host's ``TraceAnnotation`` events of given names (the engine enters
one per span while its span log is on). ``map_spans`` puts the engine's
spans (``repro.serve.spans.Span``, on ``time.perf_counter``) on the trace's
clock by the same offset as the harness's anchor. The rest is arithmetic on
those lists and the trace dictionary of ``main.Tracer.reduce``; the metric
files under ``bench/metrics`` call it.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, Iterable, List, Optional

from harness import trace as tr

MODULE_LINES = ("XLA Modules",)
PREFILL = r"jit_prefill_chunk"
DECODE = r"jit_decode_(megastep|step)\b"
# engine spans in which the host waits on the device by design
DRAINS = ("decode.drain", "admit.drain")


def extract(path: str, annotations: Iterable[str] = ("engine.step",)
            ) -> dict:
    """``modules``: [name, start_ns, dur_ns] of the first device plane's
    program events; ``annotations``: [name, start_ns, end_ns] of the host
    events with one of the given names."""
    from jax.profiler import ProfileData
    want = set(annotations)
    pd = ProfileData.from_file(path)
    dev = min((p.name for p in pd.planes if tr.DEVICE_PLANE.match(p.name)),
              default=None)
    modules, ann = [], []
    for plane in pd.planes:            # each pass yields new plane objects
        for line in plane.lines:
            if plane.name == dev and line.name in MODULE_LINES:
                modules += [[ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events]
            elif not tr.DEVICE_PLANE.match(plane.name):
                ann += [[ev.name, ev.start_ns, ev.start_ns + ev.duration_ns]
                        for ev in line.events if ev.name in want]
    return dict(modules=modules, annotations=ann)


def map_spans(spans, off: float) -> List[list]:
    """Engine spans as [name, start_ns, end_ns, sid, parent, attrs] on the
    trace's clock (``off``: trace ns minus host ns, from the anchor)."""
    return [[s.name, s.t0 * 1e9 + off, s.t1 * 1e9 + off, s.sid, s.parent,
             s.attrs] for s in spans]


def intersect(a, b):
    """Parts of the merged intervals ``a`` that ``b`` covers."""
    return tr.subtract(a, tr.subtract(a, b))


def named(prog_spans, names) -> list:
    return tr.union([(a, b) for n, a, b, *_ in prog_spans if n in names])


def work(t: dict):
    """The traced stretch less the client's sleeps: the base that
    ``device.idle_share`` divides by."""
    lo, hi = t["lo"], t["hi"]
    sleep = tr.clip(tr.union([(a, b) for lab, a, b in t["spans"]
                              if lab == "sleep"]), lo, hi)
    return tr.subtract([(lo, hi)], sleep)


def idle_share_in(t: dict, inside, outside=()) -> Optional[float]:
    """Device-idle time inside engine spans named ``inside`` and outside
    those named ``outside``, as a percentage of ``work``."""
    w = work(t)
    if tr.length(w) <= 0:
        return None
    idle = tr.subtract(w, t["busy"])
    region = tr.subtract(named(t["prog_spans"], inside),
                         named(t["prog_spans"], outside))
    return 100.0 * tr.length(intersect(idle, region)) / tr.length(w)


def module_busy(t: dict, pattern: str) -> float:
    """Device-busy nanoseconds inside the program events whose name
    matches ``pattern``."""
    rx = re.compile(pattern)
    mods = tr.union([(s, s + d) for name, s, d in t["modules"]
                     if rx.search(name)])
    return tr.length(intersect(t["busy"], mods))


def idle_causes(busy, lo: float, hi: float, prog_spans, spans
                ) -> Dict[str, float]:
    """Idle seconds of [lo, hi] (ns) by what the host was doing: the
    innermost engine span open at the time, else the harness's label there
    ('compile' over 'client', 'engine.step' or 'sleep'), else 'none'. The
    values sum to the stretch's idle time."""
    idle = tr.subtract([(lo, hi)], busy)
    marks = [(a, b, (0, n, a, -b, sid)) for n, a, b, sid, *_ in prog_spans]
    marks += [(a, b, (1, lab, i)) for i, (lab, a, b) in enumerate(spans)
              if b > a]
    cuts = sorted({lo, hi, *(x for a, b, _ in marks for x in (a, b)
                             if lo < x < hi),
                   *(x for iv in idle for x in iv)})
    opens = sorted(marks, key=lambda m: m[0])
    live: Dict[tuple, float] = {}       # open mark -> its end
    out: Dict[str, float] = collections.defaultdict(float)
    j = k = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(opens) and opens[j][0] <= a:
            live[opens[j][2]] = opens[j][1]
            j += 1
        for key in [key for key, end in live.items() if end <= a]:
            del live[key]
        while k < len(idle) and idle[k][1] <= a:
            k += 1
        if k == len(idle) or idle[k][0] > a:
            continue                    # the device is busy here
        prog = [key for key in live if key[0] == 0]
        if prog:
            label = max(prog, key=lambda key: key[2:])[1]
        else:
            labs = {key[1] for key in live}
            label = ("compile" if "compile" in labs else
                     min(labs) if labs else "none")
        out[label] += (b - a) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def clock_check(annotations, prog_spans, name: str = "engine.step"):
    """Median and largest distance (us) from each mapped ``name`` span's
    start and end to those of the profiler's annotation nearest it: how
    well the anchor's offset puts host spans on the device clock."""
    ann = sorted((a, b) for n, a, b in annotations if n == name)
    starts = [a for a, _ in ann]
    d = []
    for n, a, b, *_ in prog_spans:
        if n != name or not ann:
            continue
        i = bisect.bisect_left(starts, a)
        near = min(ann[max(i - 1, 0):i + 1], key=lambda e: abs(e[0] - a))
        d.append(max(abs(near[0] - a), abs(near[1] - b)) * 1e-3)
    if not d:
        return None
    d.sort()
    return dict(spans=len(d), median_us=d[len(d) // 2], max_us=d[-1])
