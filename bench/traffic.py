"""Inputs made from ``--seed``: open-loop serving traffic and the split
training population.

Serving: every seed gets the same multiset of prompt lengths, answer
budgets, client popularity ranks and inter-arrival gaps, each drawn at
evenly spaced quantiles of its distribution, and the seed only deals them
out in another order (:func:`dealt`: every run of ``strata`` arrivals
holds one value from each slice of the distribution), and picks the
token ids and which clients hold which rank.  So two seeds offer the
same work at the same pace, and a run's spread is the system's, not the
draw's.  The distributions come from the traffic
file: lognormal prompts, geometric budgets, Zipf clients, Poisson
arrivals (its exponential gaps).  Generator modelled on
``benchmarks/serve_traffic.make_trace``, at deployment sizes and with
every request timed from its due time.

Training: a Mixed-NonIID population in the manner of the paper's §4.1
(each client its own generative image distribution of 10 classes, noise
level cycling over five), made in bulk on the host.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


@dataclass
class ServeRequest:
    index: int
    due_s: float            # offset from the window's start
    client: int
    prompt: np.ndarray      # int32 token ids
    budget: int             # tokens to generate


def lognormal_lengths(n, median, sigma, lo, hi):
    inv = NormalDist().inv_cdf
    z = np.array([inv(q) for q in _quantiles(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def geometric_lengths(n, median, lo, hi):
    p = 1.0 - 0.5 ** (1.0 / median)          # P(X <= median) = 1/2
    q = _quantiles(n)
    x = np.ceil(np.log1p(-q) / math.log1p(-p))
    return np.clip(x, lo, hi).astype(int)


def zipf_ranks(n, exponent, population):
    w = np.arange(1, population + 1, dtype=np.float64) ** -exponent
    cdf = np.cumsum(w) / w.sum()
    return np.minimum(np.searchsorted(cdf, _quantiles(n)), population - 1)


def dealt(values, rng: np.random.Generator, strata: int) -> np.ndarray:
    """``values`` (sorted ascending) in an order drawn from ``rng`` in
    which every run of ``strata`` consecutive places holds one value of
    each of ``strata`` equal slices of the distribution: the seed picks
    which value of a slice goes to which run, and the order inside each
    run.  So every stretch of the window is offered the same mix, and a
    seed cannot load the end of the window (whose requests it cuts) with
    long answers."""
    n = len(values)
    k = max(1, min(strata, n))
    m = n // k
    v = np.asarray(values)
    head = v[:m * k].reshape(k, m)             # row s: slice s, ascending
    head = np.stack([rng.permutation(row) for row in head])  # slice s -> runs
    runs = [rng.permutation(head[:, j]) for j in range(m)]
    out = np.concatenate(runs + [rng.permutation(v[m * k:])])
    return out


def serve_trace(mix: dict, seed: int, seconds: float,
                vocab: int) -> List[ServeRequest]:
    """The window's requests, sorted by due time.  ``mix`` is the traffic
    file: ``rate_per_s``, ``prompt`` {median, sigma, min, max}, ``budget``
    {median, min, max}, ``clients`` {population, zipf}."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    pr, bu, cl = mix["prompt"], mix["budget"], mix["clients"]
    lens = lognormal_lengths(n, pr["median"], pr["sigma"], pr["min"],
                             pr["max"])
    buds = geometric_lengths(n, bu["median"], bu["min"], bu["max"])
    ranks = zipf_ranks(n, cl["zipf"], cl["population"])
    gaps = -np.log1p(-_quantiles(n))          # unit-rate exponential
    order = _rng(seed, 0)
    lens, buds, ranks, gaps = (dealt(a, order, mix.get("strata", 16))
                               for a in (lens, buds, ranks, gaps))
    # n arrivals spread over the window: the last one is due just before
    # it closes
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due = due * seconds / gaps.sum()
    holder = _rng(seed, 1).permutation(cl["population"])
    toks = _rng(seed, 2)
    return [ServeRequest(i, float(due[i]), int(holder[ranks[i]]),
                         toks.integers(0, vocab, int(lens[i]),
                                       dtype=np.int32), int(buds[i]))
            for i in range(n)]


# ---------------------------------------------------------------------------
# split-training population
# ---------------------------------------------------------------------------

NOISE_LEVELS = (0.10, 0.25, 0.20, 0.35, 0.15)


def mixed_noniid(n_clients: int, n_train: int, n_test: int, seed: int, *,
                 image_size: int = 32, n_classes: int = 10, base: int = 8):
    """Per client: (x_train, y_train, x_test, y_test), float32 images in
    [0, 1] of shape (n, image_size, image_size, 3).  Client i draws its own
    class prototypes (random base x base patterns, upsampled and
    smoothed) and noise level ``NOISE_LEVELS[i % 5]``."""
    out = []
    reps = image_size // base
    n = n_train + n_test
    for i in range(n_clients):
        rng = _rng(seed, 100 + i)
        protos = rng.standard_normal((n_classes, base, base, 3),
                                     dtype=np.float32)
        protos = protos.repeat(reps, axis=1).repeat(reps, axis=2)
        protos = (protos + np.roll(protos, 1, 1) + np.roll(protos, 1, 2)) / 3
        protos = (protos - protos.min()) / (np.ptp(protos) + 1e-8)
        y = rng.integers(0, n_classes, n).astype(np.int32)
        x = protos[y] + NOISE_LEVELS[i % len(NOISE_LEVELS)] * \
            rng.standard_normal((n, image_size, image_size, 3),
                                dtype=np.float32)
        np.clip(x, 0.0, 1.0, out=x)
        out.append((x[:n_train], y[:n_train], x[n_train:], y[n_train:]))
    return out
