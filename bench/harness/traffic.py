"""Seeded open-loop traffic: arrival schedules, queries and votes.

The arrival generator is a copy of the program's Poisson and bursty
processes (``serving/stream.arrival_times``), kept here so that a change to
the program cannot change the load. A traffic mix fixes its schedule with
its own ``schedule_seed``; the run's seed only reorders it (the gaps of a
Poisson schedule, the bursts of a bursty one), so every seed offers the
same sizes and arrivals in another order. The queries, their categories,
cost tilts, labels, vote lags and vote draws all come from the run's seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def arrival_times(kind: str, rate: float, n: int, seed: int,
                  burst: float = 16.0) -> np.ndarray:
    """(n,) sorted float64 arrival times starting near 0: Poisson at
    ``rate``, or bursts at ``rate / burst`` bringing Geometric(1/burst)
    requests each (the long-run rate is ``rate`` either way)."""
    if not rate > 0:
        raise ValueError(f"arrival rate must be positive, got {rate}")
    rng = np.random.default_rng(seed)
    if kind == "poisson":
        return np.cumsum(rng.exponential(1.0 / rate, size=n))
    if kind != "bursty":
        raise ValueError(f"unknown arrival kind {kind!r}")
    if not burst >= 1:
        raise ValueError(f"mean burst size must be >= 1, got {burst}")
    n_bursts = max(n // max(round(burst), 1) + 1, 1) * 2 + 8
    epochs = np.cumsum(rng.exponential(burst / rate, size=n_bursts))
    sizes = rng.geometric(1.0 / burst, size=n_bursts)
    times = np.repeat(epochs, sizes)
    while times.shape[0] < n:          # geometric tail undershot: extend
        extra = np.cumsum(rng.exponential(burst / rate,
                                          size=n_bursts)) + times[-1]
        sizes = rng.geometric(1.0 / burst, size=n_bursts)
        times = np.concatenate([times, np.repeat(extra, sizes)])
    return times[:n]


def schedule(mix: dict, n: int, seed: int) -> np.ndarray:
    """The mix's fixed schedule (``schedule_seed``), reordered by ``seed``:
    Poisson gaps are permuted; bursts keep their size and are permuted with
    the gap before them."""
    base = arrival_times(mix["arrivals"], float(mix["rate"]), n,
                         int(mix["schedule_seed"]),
                         burst=float(mix.get("burst", 16.0)))
    gaps = np.diff(base, prepend=0.0)
    rng = np.random.default_rng(seed)
    if mix["arrivals"] == "poisson":
        return np.cumsum(rng.permutation(gaps))
    starts = np.flatnonzero(gaps > 0)
    if starts.size == 0 or starts[0] != 0:
        starts = np.concatenate([[0], starts])
    groups = np.split(np.arange(n), starts[1:])
    order = rng.permutation(len(groups))
    out = np.concatenate([gaps[groups[g]] for g in order])
    return np.cumsum(out)


def category_token_logits(corpus: dict) -> np.ndarray:
    """(M, V) token logits per category of the synthetic corpus: a shared
    common-word pool plus one vocabulary block per category (a copy of the
    program's ``data/synth.category_token_logits``)."""
    rng = np.random.RandomState(1234)
    m, v = corpus["n_categories"], corpus["vocab_size"]
    pool, frac = corpus["common_pool"], corpus["common_frac"]
    block, temp = corpus["block_size"], corpus["topic_temp"]
    logits = np.full((m, v), -12.0, np.float32)
    logits[:, :pool] = np.log(frac / pool)
    for c in range(m):
        start = pool + c * block
        end = min(start + block, v)
        logits[c, start:end] = (np.log((1 - frac) / block)
                                + temp * rng.randn(end - start)
                                .astype(np.float32))
    return logits


def sample_tokens(rng: np.random.Generator, cats: np.ndarray,
                  corpus: dict, seq_len: int) -> np.ndarray:
    """(n, seq_len) int32 tokens, each drawn from its category's softmax
    by inverse CDF."""
    logits = category_token_logits(corpus).astype(np.float64)
    p = np.exp(logits - logits.max(axis=1, keepdims=True))
    cdf = np.cumsum(p / p.sum(axis=1, keepdims=True), axis=1)
    cdf[:, -1] = 1.0
    u = rng.random((cats.shape[0], seq_len))
    out = np.empty((cats.shape[0], seq_len), np.int32)
    for c in np.unique(cats):
        rows = cats == c
        out[rows] = np.searchsorted(cdf[c], u[rows], side="right")
    return out


@dataclasses.dataclass
class Requests:
    """Every request the run may send, indexed by arrival order."""
    arrival: np.ndarray      # (N,) seconds after the window opens
    cats: np.ndarray         # (N,) category of the query
    tokens: np.ndarray       # (N, L) int32
    pref: np.ndarray | None  # (N,) per-request cost tilt, None = no prefs
    labelled: np.ndarray     # (N,) bool: the user votes
    lag: np.ndarray          # (N,) formed batches until the vote is due
    u_vote: np.ndarray       # (N,) uniform draw that decides the vote


def make_requests(mix: dict, corpus: dict, seq_len: int, n: int,
                  seed: int) -> Requests:
    rng = np.random.default_rng([seed, 1])
    arrival = schedule(mix, n, seed)
    cats = rng.integers(0, corpus["n_categories"], n)
    tokens = sample_tokens(rng, cats, corpus, seq_len)
    pref = None
    if mix.get("tilt"):
        t = mix["tilt"]
        tilted = rng.random(n) < t["share"]
        pref = np.where(tilted, rng.uniform(t["low"], t["high"], n),
                        0.0).astype(np.float32)
    labelled = rng.random(n) < float(mix["label_rate"])
    lo, hi = mix["vote_lag_batches"]
    lag = rng.integers(lo, hi + 1, n)
    u_vote = rng.random(n)
    return Requests(arrival, cats, tokens, pref, labelled, lag, u_vote)


def votes(utils: np.ndarray, cats: np.ndarray, a1: np.ndarray,
          a2: np.ndarray, u: np.ndarray, scale: float) -> np.ndarray:
    """BTL votes: +1 (a1 preferred) with probability
    sigmoid(scale * (utils[a1, c] - utils[a2, c])), else -1."""
    z = scale * (utils[a1, cats] - utils[a2, cats])
    p = 1.0 / (1.0 + np.exp(-z))
    return np.where(u < p, 1.0, -1.0).astype(np.float32)
