"""One generator for every traffic mix.

A mix is a JSON file of parameters (``bench/traffic/<name>.json``); this
module turns it and ``--seed`` into requests.  Nothing here depends on
what the program returns: a turn's history carries a seeded synthetic
reply of the length the previous turn asked for, never the model's.

Every seed gives the same work.  Each length, think time, arrival gap
and prefix choice is drawn as a stratified quantile of its distribution
(``n`` values at quantiles ``(i + 0.5) / n``), and the values are tied
to sessions and start times by a fixed draw that no seed changes.  In
an open loop the seed picks the token ids alone: every seed sends
requests of the same lengths at the same due times, so which requests
overlap in the window, and how many tokens it serves, is the same for
all.  In a closed loop the seed also orders the backlog, which moves no
work out of the window, since every client stays busy.

Two loops:

* ``open``: sessions arrive at ``session_rate_per_s`` and send their
  turns at fixed due times (a turn is due ``think_s`` after the previous
  turn was due), whatever the server does.  Sessions start from
  ``-lead_s`` on, so the window opens on traffic in steady state; the
  last turn due before the window of each session that goes on into it
  is served in set-up, which leaves its prompt in the prefix cache as a
  steady state would.
* ``closed``: one client per slot sends its next request when the
  previous one completes.  Set-up starts every client on a request
  already part-way through: its prompt carries the tokens it would have
  generated so far, and it asks only for the rest, so the window opens
  with slots at mixed progress.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np

#: fixed draw that ties stratified values to sessions; no seed changes it
STRUCTURE_SEED = 20240711


@dataclasses.dataclass
class Request:
    idx: int                      # position in the mix's request list
    prompt: np.ndarray            # int32 token ids
    max_new: int                  # tokens to generate (no EOS: all of them)
    due: Optional[float] = None   # seconds from the window's start (open)
    session: int = -1
    prefix_id: int = -1           # shared prefix the prompt starts with
    prefix_len: int = 0


@dataclasses.dataclass
class Traffic:
    loop: str
    requests: List[Request]       # open: due in order; closed: backlog
    warm: List[Request]           # served in set-up (prefixes, histories)
    first_wave: List[Request]     # closed: one mid-flight request a client
    drain_s: float


# ---------------------------------------------------------------------------
# stratified quantiles


def _cdf_inv(dist: dict):
    """(cdf, inverse cdf) of a distribution given as a mix parameter."""
    kind = dist["dist"]
    if kind == "lognormal":
        nd = NormalDist()
        mu, s = math.log(dist["median"]), dist["sigma"]
        return (lambda x: nd.cdf((math.log(x) - mu) / s),
                lambda q: math.exp(mu + s * nd.inv_cdf(q)))
    if kind == "exponential":
        m = dist["mean"]
        return (lambda x: 1 - math.exp(-x / m),
                lambda q: -m * math.log(1 - q))
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return (lambda x: (x - lo) / (hi - lo),
                lambda q: lo + q * (hi - lo))
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: dict, n: int, *, integer: bool = True) -> np.ndarray:
    """``n`` values at the quantiles ``(i + 0.5) / n`` of ``dist``,
    truncated to ``[min, max]`` (the quantiles are taken inside the
    truncated range, so no value piles up on a bound)."""
    cdf, inv = _cdf_inv(dist)
    lo, hi = dist.get("min"), dist.get("max")
    q0 = cdf(lo) if lo is not None and dist["dist"] != "uniform" else 0.0
    q1 = cdf(hi) if hi is not None and dist["dist"] != "uniform" else 1.0
    q = q0 + (np.arange(n) + 0.5) / n * (q1 - q0)
    v = np.array([inv(x) for x in q])
    if lo is not None:
        v = np.maximum(v, lo)
    if hi is not None:
        v = np.minimum(v, hi)
    return np.rint(v).astype(np.int64) if integer else v


def zipf_counts(k: int, s: float, n: int) -> np.ndarray:
    """How many of ``n`` picks go to each of ``k`` items under Zipf(s),
    rounded by largest remainder so that they sum to ``n``."""
    w = 1.0 / np.arange(1, k + 1) ** s
    exact = n * w / w.sum()
    c = np.floor(exact).astype(np.int64)
    rest = np.argsort(-(exact - c), kind="stable")[: n - c.sum()]
    c[rest] += 1
    return c


def _tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, n, dtype=np.int64).astype(np.int32)


# ---------------------------------------------------------------------------
# the generator


def generate(mix: dict, seed: int, seconds: float, *, vocab: int,
             max_len: int, slots: int) -> Traffic:
    """Requests of ``mix`` for a window of ``seconds`` (see module
    docstring)."""
    if mix["loop"] == "open":
        return _open(mix, seed, seconds, vocab, max_len)
    if mix["loop"] == "closed":
        return _closed(mix, seed, seconds, vocab, max_len, slots)
    raise ValueError(f"unknown loop {mix['loop']!r}")


def _seed_rng(seed: int, stream: int):
    return np.random.default_rng([seed % 2 ** 63, stream])


def _prefixes(mix: dict, rng, vocab: int):
    sp = mix.get("shared_prefix")
    if not sp:
        return []
    lens = stratified(sp["tokens"], sp["count"])
    # the most popular prefix gets a fixed length, whatever the seed
    order = np.random.default_rng(STRUCTURE_SEED).permutation(sp["count"])
    return [_tokens(rng, int(lens[order[i]]), vocab)
            for i in range(sp["count"])]


def _open(mix, seed, seconds, vocab, max_len) -> Traffic:
    lead = float(mix["lead_s"])
    span = lead + seconds
    n = max(1, int(round(mix["session_rate_per_s"] * span)))
    fixed = np.random.default_rng(STRUCTURE_SEED)
    rng = _seed_rng(seed, 1)
    prefixes = _prefixes(mix, _seed_rng(seed, 2), vocab)
    sess = mix["sessions"]
    turns = fixed.permutation(stratified(
        {"dist": "uniform", "min": sess["turns"]["min"],
         "max": sess["turns"]["max"] + 1}, n, integer=False
    ).astype(np.int64).clip(sess["turns"]["min"], sess["turns"]["max"]))
    n_turns = int(turns.sum())
    user = fixed.permutation(stratified(mix["user_tokens"], n_turns))
    out = fixed.permutation(stratified(mix["output_tokens"], n_turns))
    think = fixed.permutation(stratified(sess["think_s"], n_turns,
                                         integer=False))
    if prefixes:
        sp = mix["shared_prefix"]
        pick = fixed.permutation(np.repeat(
            np.arange(sp["count"]),
            zipf_counts(sp["count"], sp["zipf_s"], n)))
    # arrival gaps: a fixed multiset in a fixed order, scaled to fill the
    # span; sessions keep their start times whatever the seed
    gaps = stratified({"dist": "exponential",
                       "mean": 1.0 / mix["session_rate_per_s"]}, n,
                      integer=False)
    gaps = fixed.permutation(gaps * span / gaps.sum())
    starts = np.cumsum(gaps) - gaps[0] - lead
    order = fixed.permutation(n)
    window, warm, idx = [], [], 0
    warm += [Request(-1 - i, p, 1, prefix_id=i, prefix_len=len(p))
             for i, p in enumerate(prefixes)]
    t0 = np.concatenate([[0], np.cumsum(turns)])
    for k, s in enumerate(order):
        t = starts[k]
        pid = int(pick[s]) if prefixes else -1
        hist = [prefixes[pid]] if prefixes else []
        last_before: Optional[Request] = None
        for j in range(int(turns[s])):
            g = t0[s] + j
            if j:
                t += think[g]
            hist.append(_tokens(rng, int(user[g]), vocab))
            prompt = np.concatenate(hist)
            new = int(out[g])
            if len(prompt) + new > max_len:
                break
            req = Request(idx, prompt, new, due=float(t), session=int(s),
                          prefix_id=pid,
                          prefix_len=len(prefixes[pid]) if prefixes else 0)
            idx += 1
            if t < 0:
                last_before = req
            elif t < seconds:
                if last_before is not None:
                    warm.append(dataclasses.replace(last_before, max_new=1))
                    last_before = None
                window.append(req)
            if mix.get("history", False):
                hist.append(_tokens(rng, new, vocab))   # synthetic reply
            else:
                hist = hist[:1]
    window.sort(key=lambda r: (r.due, r.idx))
    return Traffic("open", window, warm, [], float(mix["drain_s"]))


def _closed(mix, seed, seconds, vocab, max_len, slots) -> Traffic:
    per_client = int(mix["requests_per_client"])
    n = slots * per_client
    fixed = np.random.default_rng(STRUCTURE_SEED)
    rng = _seed_rng(seed, 1)
    plen = fixed.permutation(stratified(mix["prompt_tokens"], n))
    out = fixed.permutation(stratified(mix["output_tokens"], n))
    if (plen + out).max() > max_len:
        raise ValueError("a request of the mix exceeds max_len")
    # the first wave (one request a client, part-way through, so that
    # the slots hold requests at mixed progress when the window opens)
    # is the same for every seed; the seed orders the backlog
    order = np.arange(n)
    order[slots:] = slots + rng.permutation(n - slots)
    done_share = fixed.permutation((np.arange(slots) + 0.5) / slots)
    first, backlog = [], []
    for k, s in enumerate(order):
        prompt = _tokens(rng, int(plen[s]), vocab)
        new = int(out[s])
        if k < slots:
            age = int(done_share[k] * new)
            prompt = np.concatenate([prompt, _tokens(rng, age, vocab)])
            first.append(Request(k, prompt, new - age))
        else:
            backlog.append(Request(k, prompt, new))
    return Traffic("closed", backlog, [], first, float(mix["drain_s"]))
