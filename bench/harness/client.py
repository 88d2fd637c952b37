"""The client loop: sends the traffic to the engine on its schedule (open
loop) or as clients free up (closed loop), steps the engine, and stamps
every token on its own clock when it first sees it in the request's output.

Latency runs from each request's due time, not from when it was sent, so a
stalled loop charges the wait to the requests it delayed. A request counts
in the window when it is due inside it. After the window the loop keeps the
traffic going and drains until every counted request has finished, or the
drain limit passes; what is unfinished then is failed.

The loop also records what the harness was doing and when (``spans``), the
engine's counters at the window's edges, and the prompt tokens each request
brought to admission and how many of them the prefix cache served.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

from harness.traffic import Job


@dataclasses.dataclass
class Record:
    uid: int
    prompt_len: int
    max_new: int
    due: float                        # host clock (perf_counter seconds)
    counted: bool = False
    sent: float = 0.0
    first_seen: Optional[float] = None
    last_seen: Optional[float] = None
    seen: int = 0
    stamps: List[float] = dataclasses.field(default_factory=list)
    admit_start: Optional[float] = None
    admit_done: Optional[float] = None
    admitted_at: Optional[float] = None   # when the pool mapped its pages
    shared: float = 0.0               # prompt tokens the prefix cache served
    done: bool = False
    rejected: bool = False
    req: object = None


@dataclasses.dataclass
class Outcome:
    records: List[Record]
    window: tuple                     # (start, end) host clock
    spans: List[tuple]                # (label, start, end) host clock
    counters: Dict[str, Dict[str, float]]   # "start" / "end" snapshots
    pool_used: List[tuple]            # (time, pages used) after each step
    drain_s: float
    steps: int
    t0: float                         # schedule start: the first due time


def counters(eng) -> Dict[str, float]:
    s = eng.pool.stats
    return dict(row_tokens=eng.row_tokens, row_dispatches=eng.row_dispatches,
                decode_dispatches=eng.decode_dispatches,
                tokens_skipped=s["tokens_skipped"],
                prefix_hits=s["prefix_hits"],
                prefix_misses=s["prefix_misses"])


def run(eng, jobs: List[Job], mix: dict, seconds: float, *,
        marks=(), clock=time.perf_counter) -> Outcome:
    """Drive ``jobs`` through ``eng``. ``marks`` holds (offset, callback)
    pairs: each callback runs once the loop passes that many seconds after
    the window's start, and may return a (delay, callback) pair to run that
    many seconds after it (the traced run starts and stops the profiler
    so)."""
    from repro.serve.engine import Request
    closed = mix["loop"] == "closed"
    lead, drain_s = mix["lead_in_s"], mix["drain_s"]
    t0 = clock()
    w0, w1 = t0 + lead, t0 + lead + seconds
    records: List[Record] = []
    inflight: Dict[int, Record] = {}
    spans: List[tuple] = []
    pool_used: List[tuple] = []
    snap: Dict[str, Dict[str, float]] = {}
    nxt = 0                                   # next job to send
    free_at = [t0] * mix.get("clients", 0)    # closed loop: client due times
    client_of: Dict[int, int] = {}
    phase = "lead"
    steps = 0
    marks = sorted(((w0 + off, fn) for off, fn in marks),
                   key=lambda m: m[0])

    def send(job: Job, due: float, now: float) -> Record:
        rec = Record(job.uid, len(job.prompt), job.max_new, due,
                     counted=w0 <= due < w1, sent=now)
        rec.req = Request(job.uid, prompt=[int(t) for t in job.prompt],
                          max_new=job.max_new)
        rec.req.t_arrival = due
        eng.submit(rec.req)
        records.append(rec)
        inflight[job.uid] = rec
        return rec

    while True:
        now = clock()
        if phase == "lead" and now >= w0:
            phase = "window"
            snap["start"] = counters(eng)
        if phase == "window" and now >= w1:
            phase = "drain"
            snap["end"] = counters(eng)
        while marks and now >= marks[0][0]:
            nxt_mark = marks.pop(0)[1]()
            if nxt_mark:                      # (delay, callback) to follow
                marks.append((clock() + nxt_mark[0], nxt_mark[1]))
                marks.sort(key=lambda m: m[0])
        if phase == "drain":
            left = [r for r in records if r.counted and not (r.done or
                                                              r.rejected)]
            if not left or now >= w1 + drain_s:
                break
        # ---- send what is due
        ts = clock()
        if closed:
            for c, due in enumerate(free_at):
                if due is not None and due <= now and nxt < len(jobs):
                    rec = send(jobs[nxt], due, now)
                    client_of[rec.uid] = c
                    free_at[c] = None
                    nxt += 1
        else:
            while nxt < len(jobs) and t0 + jobs[nxt].due <= now:
                send(jobs[nxt], t0 + jobs[nxt].due, now)
                nxt += 1
        pending_before = {id(r) for r in eng.pending}
        spans.append(("client", ts, clock()))
        # ---- step the engine, or wait for the next arrival
        if eng.idle:
            if closed:
                nd = min((d for d in free_at if d is not None), default=None)
            else:
                nd = t0 + jobs[nxt].due if nxt < len(jobs) else None
            edge = w0 if phase == "lead" else (w1 if phase == "window"
                                               else w1 + drain_s)
            target = min(x for x in (nd, edge, marks[0][0] if marks
                                     else None) if x is not None)
            ts = clock()
            if target > ts:
                time.sleep(min(target - ts, 0.002))
            spans.append(("sleep", ts, clock()))
            continue
        ts = clock()
        skipped0 = eng.pool.stats["tokens_skipped"]
        eng.step()
        steps += 1
        te = clock()
        spans.append(("engine.step", ts, te))
        # ---- see what came out
        used = eng.pool.used
        pool_used.append((te, used))
        pending_after = {id(r) for r in eng.pending}
        admitted = [rec for rec in inflight.values()
                    if rec.admitted_at is None
                    and id(rec.req) in pending_before
                    and id(rec.req) not in pending_after]
        if admitted:
            share = (eng.pool.stats["tokens_skipped"] - skipped0) \
                / len(admitted)
            for rec in admitted:
                rec.admitted_at, rec.shared = te, share
        for uid, rec in list(inflight.items()):
            r = rec.req
            if rec.admit_start is None and r.t_admit_start:
                rec.admit_start = r.t_admit_start
            n = len(r.out)
            if n > rec.seen:
                if rec.first_seen is None:
                    rec.first_seen = te
                rec.stamps.extend([te] * (n - rec.seen))
                rec.last_seen, rec.seen = te, n
            if r.done or r.rejected:
                rec.done, rec.rejected = r.done, r.rejected
                rec.admit_done = r.t_admit or None
                del inflight[uid]
                if closed:
                    free_at[client_of.pop(uid)] = te
        spans.append(("client", te, clock()))
    while marks:                             # marks past the drain
        follow = marks.pop(0)[1]()
        if follow:
            marks.append((0.0, follow[1]))
    return Outcome(records, (w0, w1), spans, snap, pool_used,
                   drain_s=max(0.0, clock() - w1), steps=steps, t0=t0)
