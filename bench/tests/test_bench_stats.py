"""Tail and rate arithmetic uses every request and the whole window."""
import pytest

from harness import stats
from harness.client import Outcome, Record


def outcome(records, window=(10.0, 20.0)):
    return Outcome(records, window, [], {}, [], 0.0, 0, 0.0)


def rec(uid, due, stamps, counted=True, done=True):
    r = Record(uid, 10, len(stamps), due, counted=counted)
    r.stamps = list(stamps)
    r.seen = len(stamps)
    r.first_seen = stamps[0] if stamps else None
    r.last_seen = stamps[-1] if stamps else None
    r.done = done
    return r


def load(name):
    from harness.main import BENCH, load_module
    return load_module(BENCH / "metrics" / f"{name}.py")


def test_percentile_over_all_values():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == pytest.approx(95.05)
    assert stats.percentile([], 95) is None


def test_ttft_tail_counts_every_request_from_its_due_time():
    recs = [rec(i, 10.0 + 0.1 * i, [10.0 + 0.1 * i + 0.01 * (i + 1)])
            for i in range(20)]
    recs.append(rec(99, 25.0, [25.5], counted=False))   # due after window
    ctx = dict(counted=[r for r in recs if r.counted], out=outcome(recs))
    got = load("client.ttft_p95_ms").read(ctx)
    want = stats.percentile([0.01 * (i + 1) for i in range(20)], 95) * 1e3
    assert got == pytest.approx(want)


def test_tpot_averages_a_requests_drains():
    # 9 tokens: 1 at admission, then 8 arriving together 80 ms later
    r = rec(1, 10.0, [10.1] + [10.18] * 8)
    assert stats.tpot(r.first_seen, r.last_seen, r.seen) == \
        pytest.approx(0.01)
    ctx = dict(counted=[r, rec(2, 10.0, [10.5])], out=outcome([r]))
    assert load("client.tpot_p95_ms").read(ctx) == pytest.approx(10.0)


def test_output_rate_is_tokens_in_window_over_window():
    recs = [rec(1, 5.0, [9.0, 10.0, 12.0, 19.99, 20.0, 21.0], counted=False),
            rec(2, 11.0, [11.5, 15.0])]
    ctx = dict(counted=[recs[1]], out=outcome(recs))
    assert load("output_tok_s").read(ctx) == pytest.approx(5 / 10.0)


def test_tokens_per_dispatch_over_the_window():
    o = outcome([])
    o.counters = {"start": dict(row_tokens=100, row_dispatches=50),
                  "end": dict(row_tokens=900, row_dispatches=150)}
    assert load("sched.tokens_per_dispatch").read(dict(out=o)) == 8.0


def test_prefix_hit_share_uses_admissions_in_window():
    a = rec(1, 10.0, [11.0])
    a.admitted_at, a.shared = 10.5, 1024
    b = rec(2, 12.0, [13.0])
    b.admitted_at, b.shared = 12.1, 0
    c = rec(3, 1.0, [2.0], counted=False)
    c.admitted_at, c.shared = 1.5, 10
    for r, n in ((a, 1200), (b, 800), (c, 10)):
        r.prompt_len = n
    got = load("pool.prefix_hit_share").read(dict(out=outcome([a, b, c])))
    assert got == pytest.approx(100 * 1024 / 2000)


def test_spread_is_iqr_over_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


@pytest.mark.parametrize("metric,case", [("client.ttft_p95_ms", "never"),
                                         ("client.tpot_p95_ms", "never"),
                                         ("client.tpot_p95_ms", "stuck")])
def test_a_failed_request_raises_the_tail(metric, case):
    # 19 requests served alike; the 20th did not finish before the drain
    # limit (20 + 5 s): it counts up to the limit, not out of the tail
    recs = [rec(i, 10.0 + 0.1 * i,
                [10.0 + 0.1 * i + 0.2 + 0.01 * k for k in range(5)])
            for i in range(19)]
    ok = load(metric).read(dict(counted=recs, out=outcome(recs)))
    bad = rec(19, 15.0, [15.5] if case == "stuck" else [], done=False)
    bad.max_new = 5
    o = outcome(recs + [bad])
    o.drain_s = 5.0
    got = load(metric).read(dict(counted=recs + [bad], out=o))
    assert got > 1.5 * ok
