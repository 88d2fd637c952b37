"""The traffic generator: deterministic per seed, the stated distributions,
the same work for every seed, sessions that grow their prompt."""
import json
import pathlib

import numpy as np
import pytest

from harness import traffic

MIXES = pathlib.Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat_prefix", "chat_burst",
                                  "longdoc_closed", "decode_closed"])
def test_same_seed_same_requests(name):
    a = traffic.generate(mix(name), 2**31 + 99, 10, 50000)
    b = traffic.generate(mix(name), 2**31 + 99, 10, 50000)
    c = traffic.generate(mix(name), 2**31 + 100, 10, 50000)
    assert [(j.due, j.max_new, j.prompt.tolist()) for j in a] == \
        [(j.due, j.max_new, j.prompt.tolist()) for j in b]
    assert [j.prompt.tolist() for j in a] != [j.prompt.tolist() for j in c]


@pytest.mark.parametrize("name", ["chat_burst", "longdoc_closed",
                                  "decode_closed"])
def test_every_seed_gets_the_same_sizes(name):
    """Stratified draws: another seed reorders the sizes, never changes
    them, so the work of a run does not depend on its seed."""
    a = traffic.generate(mix(name), 1, 10, 50000)
    b = traffic.generate(mix(name), 2, 10, 50000)
    n = min(len(a), len(b), 512)
    sa = sorted((len(j.prompt), j.max_new) for j in a[:n])
    sb = sorted((len(j.prompt), j.max_new) for j in b[:n])
    assert [x for x, _ in sa] == [x for x, _ in sb]
    assert sorted(y for _, y in sa) == sorted(y for _, y in sb)
    assert [len(j.prompt) for j in a[:n]] != [len(j.prompt) for j in b[:n]]


@pytest.mark.parametrize("key", ["prompt", "output"])
def test_lognormal_median_and_clip(key):
    m = mix("chat_burst")
    spec = m[key]
    v = traffic.ints(spec, 4096, np.random.default_rng(0))
    assert abs(np.median(v) - spec["median"]) <= 1
    assert v.min() >= spec["min"] and v.max() <= spec["max"]
    # sigma: the log-quartile distance of a lognormal is 1.349 sigma
    q1, q3 = np.percentile(np.log(v), [25, 75])
    assert abs((q3 - q1) / 1.349 - spec["sigma"]) < 0.03


def test_gamma_arrivals_are_bursty_and_fill_the_horizon():
    m = mix("chat_burst")
    jobs = traffic.generate(m, 5, 20, 50000)
    due = np.array([j.due for j in jobs])
    gaps = np.diff(due)
    assert len(jobs) == round(m["rate"] * traffic.horizon(m, 20))
    cv = gaps.std() / gaps.mean()
    assert 2.0 < cv < 3.5                # CV 3, truncated by the quantiles
    assert due[-1] < traffic.horizon(m, 20)
    assert np.all(gaps >= 0)


def test_poisson_session_starts_and_turn_counts():
    m = mix("chat_prefix")
    jobs = traffic.generate(m, 11, 45, 200064)
    H = traffic.horizon(m, 45)
    starts = sorted(j.due for j in jobs if j.turn == 0)
    mean_turns = traffic.mean_of(m["sessions"]["turns"])
    assert len(starts) == round(m["rate"] / mean_turns * H)
    turns = np.bincount([j.session for j in jobs])
    turns = turns[turns > 0]
    assert set(turns.tolist()) <= {1, 2, 3, 4}
    # rate in requests/s: session starts times mean turns, less the turns
    # that fall past the horizon or the prompt cap
    assert 0.75 * m["rate"] * H < len(jobs) <= m["rate"] * H * 1.05


def test_session_turns_extend_the_previous_prompt():
    m = mix("chat_prefix")
    jobs = traffic.generate(m, 3, 45, 200064)
    by = {}
    for j in jobs:
        by.setdefault(j.session, []).append(j)
    systems = {tuple(j.prompt[:1024]) for j in jobs}
    assert len(systems) == 4             # four shared system prompts
    multi = [v for v in by.values() if len(v) > 1]
    assert multi
    for turns in multi:
        turns.sort(key=lambda j: j.turn)
        for a, b in zip(turns, turns[1:]):
            assert b.due > a.due
            assert len(b.prompt) > len(a.prompt)
            assert np.array_equal(b.prompt[:len(a.prompt)], a.prompt)
    assert max(len(j.prompt) for j in jobs) <= m["prompt_cap"]


def test_system_prompt_popularity():
    m = dict(mix("chat_prefix"), rate=4.0)    # enough sessions to count
    jobs = traffic.generate(m, 8, 45, 200064)
    firsts = [tuple(j.prompt[:1024]) for j in jobs if j.turn == 0]
    counts = sorted((firsts.count(s) for s in set(firsts)), reverse=True)
    share = np.array(counts) / len(firsts)
    assert np.allclose(share, m["system_prompts"]["popularity"], atol=0.05)


def test_longest_request_fits_every_engine():
    cfgs = pathlib.Path(__file__).resolve().parents[1] / "configs"
    bench = json.loads((cfgs.parents[1] / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        conf = next(c for c in bench["configs"] if c["name"] == w["config"])
        cfg = json.loads((cfgs.parents[1] / conf["file"]).read_text())
        assert traffic.max_request_tokens(mix(w["traffic"])) <= \
            cfg["engine"]["max_len"]
