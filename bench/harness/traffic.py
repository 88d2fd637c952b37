"""One general generator for every traffic mix: a mix is a JSON file of
parameters under ``bench/traffic``; this module turns it into requests.

Every seed gets the same multiset of sizes, gaps and turn counts, in another
order: each distribution is sampled by stratified quantiles (block by block,
so any prefix of the list is balanced too) and shuffled by the seed. The
seed therefore changes which request is long and when a burst comes, not how
much work a run holds. Token ids are drawn from the seed.

Open loops (``"loop": "open"``) give every request a due time on the
schedule's clock (0 = start of the lead-in). Sessions add turns whose prompt
is the previous prompt, a synthetic answer and a new user turn. Closed loops
(``"loop": "closed"``) give a list of jobs that the clients take in order;
their due times are set by the client loop when a client's previous request
completes.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np
from scipy.special import ndtri

BLOCK = 64          # stratification block: any prefix of a list is balanced


@dataclasses.dataclass
class Job:
    uid: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    due: Optional[float] = None  # seconds on the schedule clock (open loop)
    session: int = -1
    turn: int = 0


def _ppf(spec: dict, p: np.ndarray) -> np.ndarray:
    """Quantile function of one length/time distribution at probabilities p."""
    d = spec["dist"]
    if d == "lognormal":
        v = spec["median"] * np.exp(spec["sigma"] * ndtri(p))
    elif d == "exponential":
        v = -spec.get("mean", 1.0) * np.log1p(-p)
    elif d == "gamma":
        from scipy.stats import gamma
        k = 1.0 / spec["cv"] ** 2
        v = gamma.ppf(p, k, scale=spec.get("mean", 1.0) / k)
    elif d == "geometric":
        lo, hi = spec["min"], spec["max"]
        ks = np.arange(lo, hi + 1)
        w = spec["p"] * (1 - spec["p"]) ** (ks - lo)
        cdf = np.cumsum(w / w.sum())
        v = ks[np.minimum(np.searchsorted(cdf, p, side="right"), len(ks) - 1)]
    else:
        raise ValueError(f"unknown distribution {d!r}")
    if "min" in spec and d != "geometric":
        v = np.clip(v, spec["min"], spec["max"])
    return v


def stratified(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n draws of ``spec``: each block of BLOCK holds the block's evenly
    spaced quantiles, shuffled by ``rng``."""
    out = []
    while len(out) < n:
        m = min(BLOCK, n - len(out))
        p = (np.arange(m) + 0.5) / m
        out.extend(_ppf(spec, p)[rng.permutation(m)])
    return np.asarray(out[:n], float)


def ints(spec: dict, n: int, rng) -> np.ndarray:
    return np.maximum(np.rint(stratified(spec, n, rng)).astype(int), 1)


def mean_of(spec: dict) -> float:
    """Mean of a distribution as the stratified sampler draws it."""
    p = (np.arange(4096) + 0.5) / 4096
    return float(np.mean(_ppf(spec, p)))


def _gaps(spec: dict, n: int, horizon: float, rng) -> np.ndarray:
    """n inter-arrival gaps of the given shape, scaled to fill ``horizon``."""
    shape = dict(spec, mean=1.0)
    if shape["dist"] == "poisson":
        shape["dist"] = "exponential"
    g = stratified(shape, n, rng)
    return g * (horizon / g.sum())


def horizon(mix: dict, seconds: float) -> float:
    return mix["lead_in_s"] + seconds + mix["drain_s"]


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[Job]:
    """Requests for one run of ``seconds`` measured seconds under ``mix``."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    tok = lambda n: rng.integers(1, vocab, n, dtype=np.int32)
    H = horizon(mix, seconds)
    if mix["loop"] == "closed":
        # enough jobs for every client to keep busy at any plausible speed
        n = mix.get("jobs", 4096)
        lens, outs = ints(mix["prompt"], n, rng), ints(mix["output"], n, rng)
        return [Job(i, tok(int(s)), int(o)) for i, (s, o) in
                enumerate(zip(lens, outs))]
    if "sessions" not in mix:
        n = max(1, int(round(mix["rate"] * H)))
        due = np.cumsum(_gaps(mix["arrival"], n, H, rng)) - 0.0
        due = np.concatenate([[0.0], due[:-1]])
        lens, outs = ints(mix["prompt"], n, rng), ints(mix["output"], n, rng)
        return [Job(i, tok(int(s)), int(o), due=float(t)) for i, (s, o, t) in
                enumerate(zip(lens, outs, due))]
    return _sessions(mix, H, rng, tok)


def _sessions(mix: dict, H: float, rng, tok) -> List[Job]:
    ses = mix["sessions"]
    turns_mean = mean_of(ses["turns"])
    n_s = max(1, int(round(mix["rate"] / turns_mean * H)))
    starts = np.concatenate([[0.0], np.cumsum(
        _gaps(mix["arrival"], n_s, H, rng))[:-1]])
    turns = ints(ses["turns"], n_s, rng)
    n_t = int(turns.sum())
    think = stratified(ses["think_s"], n_t, rng)
    users, answers = ints(mix["user_turn"], n_t, rng), \
        ints(mix["answer"], n_t, rng)
    outs = ints(mix["output"], n_t, rng)
    sp = mix["system_prompts"]
    pop = np.asarray(sp["popularity"], float)
    # exact popularity counts per block, shuffled: which prompt a session
    # opens with is stratified like every other choice
    which = []
    while len(which) < n_s:
        cnt = np.floor(pop / pop.sum() * BLOCK + 0.5).astype(int)
        block = np.repeat(np.arange(len(pop)), cnt)
        which.extend(block[rng.permutation(len(block))])
    systems = [tok(sp["length"]) for _ in pop]
    cap = mix["prompt_cap"]
    jobs: List[Job] = []
    k = 0
    for s in range(n_s):
        hist = systems[which[s]]
        t = starts[s]
        for j in range(int(turns[s])):
            if j:
                t = t + think[k]
                hist = np.concatenate([hist, tok(int(answers[k]))])
            prompt = np.concatenate([hist, tok(int(users[k]))])
            k += 1
            if len(prompt) > cap or t >= H:
                break               # the session ends at the prompt cap
            jobs.append(Job(0, prompt, int(outs[k - 1]), due=float(t),
                            session=s, turn=j))
            hist = prompt
    jobs.sort(key=lambda j: j.due)
    for i, j in enumerate(jobs):
        j.uid = i
    return jobs


def max_request_tokens(mix: dict) -> int:
    """Longest prompt + output the mix can produce (engine sizing check)."""
    out = mix["output"]["max"]
    if "sessions" in mix:
        return mix["prompt_cap"] + out
    return mix["prompt"]["max"] + out
