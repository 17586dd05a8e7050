"""The traffic generator repeats from its seed, and every seed offers the
same work in another order."""
import json
from pathlib import Path

import numpy as np

from bench import traffic

ROOT = Path(__file__).resolve().parents[2]
MIX = json.loads((ROOT / "bench" / "workloads" / "serve.qwen2.poisson.json")
                 .read_text())


def _summary(trace):
    return ([r.due_s for r in trace], [r.client for r in trace],
            [r.prompt.tolist() for r in trace], [r.budget for r in trace])


def test_same_seed_same_trace():
    a = traffic.serve_trace(MIX, 2**31 + 11, 5.0, 151936)
    b = traffic.serve_trace(MIX, 2**31 + 11, 5.0, 151936)
    assert _summary(a) == _summary(b)


def test_seeds_share_the_work_not_the_order():
    a = traffic.serve_trace(MIX, 1, 20.0, 151936)
    b = traffic.serve_trace(MIX, 2, 20.0, 151936)
    assert len(a) == len(b) == round(MIX["rate_per_s"] * 20.0)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                     for r in b)
    assert sorted(r.budget for r in a) == sorted(r.budget for r in b)
    assert _summary(a) != _summary(b)


def test_shapes_follow_the_mix():
    tr = traffic.serve_trace(MIX, 7, 60.0, 151936)
    lens = np.array([len(r.prompt) for r in tr])
    buds = np.array([r.budget for r in tr])
    due = np.array([r.due_s for r in tr])
    assert lens.min() >= MIX["prompt"]["min"]
    assert lens.max() <= MIX["prompt"]["max"]
    assert abs(np.median(lens) - MIX["prompt"]["median"]) <= 8
    assert buds.min() >= MIX["budget"]["min"]
    assert buds.max() <= MIX["budget"]["max"]
    assert abs(np.median(buds) - MIX["budget"]["median"]) <= 2
    assert np.all(np.diff(due) >= 0) and due[0] == 0 and due[-1] < 60.0
    assert all(0 <= r.client < MIX["clients"]["population"] for r in tr)
    # Zipf: the most popular client sends several percent of requests
    top = np.bincount([r.client for r in tr]).max()
    assert top / len(tr) > 0.05
    assert all(r.prompt.dtype == np.int32 for r in tr)


def test_zipf_and_geometric_quantiles():
    r = traffic.zipf_ranks(1000, 1.1, 4096)
    assert r.min() == 0 and r.max() < 4096
    g = traffic.geometric_lengths(1001, 64, 1, 10_000)
    assert abs(int(np.median(g)) - 64) <= 1


def test_population_repeats_from_its_seed():
    a = traffic.mixed_noniid(3, 8, 4, seed=5)
    b = traffic.mixed_noniid(3, 8, 4, seed=5)
    c = traffic.mixed_noniid(3, 8, 4, seed=6)
    for (xa, ya, ta, tya), (xb, yb, tb, tyb) in zip(a, b):
        assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
        assert xa.shape == (8, 32, 32, 3) and ta.shape == (4, 32, 32, 3)
        assert xa.dtype == np.float32 and 0 <= xa.min() <= xa.max() <= 1
    assert not np.array_equal(a[0][0], c[0][0])


def test_every_stretch_is_offered_the_same_mix():
    """Each run of ``strata`` arrivals holds one value of each slice, so
    the answer tokens due in any stretch hardly depend on the seed."""
    k, m = 16, 20
    vals = np.arange(k * m + 3)
    for seed in (1, 2):
        out = traffic.dealt(vals, np.random.default_rng(seed), k)
        assert sorted(out) == sorted(vals)
        for j in range(m):
            assert sorted(out[j * k:(j + 1) * k] // m) == list(range(k))
    tails = [sum(r.budget for r in traffic.serve_trace(MIX, s, 30.0, 100)
                 if r.due_s > 15.0) for s in range(1, 6)]
    assert (max(tails) - min(tails)) / np.mean(tails) < 0.06
