"""The program's own spans (``repro.utils.spans``): recorded into a
``jax.profiler`` trace at the trainer's and the serving engine's layer
boundaries, with the counts the caller holds, and nothing at all when no
profiler session is open."""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import get_config
from repro.core import masks as masks_mod
from repro.core.adasplit import AdaSplitHParams, AdaSplitTrainer
from repro.data.synthetic import mixed_noniid
from repro.launch.steps import init_serve_params
from repro.serve import ContinuousEngine, Request
from repro.utils.spans import span


def _traced(tmp_path, fn):
    """Run ``fn`` under a profiler session; return the program's spans as
    (start_ns, end_ns, name, args), in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro:"):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name[len("repro:"):], dict(ev.stats)))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def _named(spans, name):
    return [s for s in spans if s[2] == name]


def test_epoch_scan_train_spans(tmp_path):
    cfg = dataclasses.replace(get_config("lenet-cifar"),
                              conv_channels=(4, 4, 4, 4, 4))
    C, B, T = 2, 8, 2
    clients = mixed_noniid(n_clients=C, n_per_client=B * T, n_test=8,
                           seed=0)
    tr = AdaSplitTrainer(cfg, AdaSplitHParams(
        rounds=3, kappa=0.5, batch_size=B, epoch_scan=True, seed=1),
        clients)
    tr.train(eval_every=3)                  # compiles outside the trace
    spans = _traced(tmp_path, lambda: tr.train(eval_every=3))

    top, = _named(spans, "train")
    assert all(_inside(s, top) for s in spans)
    epochs = _named(spans, "train.epoch")
    # kappa 0.5 of 3 rounds: 2 local rounds, then 1 global
    assert len(epochs) == 2
    for name in ("train.feed", "train.dispatch", "train.bill"):
        got = _named(spans, name)
        assert len(got) == 2
        assert all(_inside(s, e) for s, e in zip(got, epochs))
    sync, = _named(spans, "train.sync")         # the global epoch's fetch
    assert _inside(sync, epochs[1])
    ev, = _named(spans, "train.eval")
    assert ev[0] >= epochs[1][1]
    # only the host copies count what they move (their rate is logged)
    assert {s[2] for s in spans if s[3]} == {"train.stack", "train.put"}

    x, y = clients[0].x, clients[0].y
    per_round = T * C * B * (x[0].nbytes + y[0].nbytes)
    feeds = _named(spans, "train.feed")
    for feed, rounds in zip(feeds, (2, 1)):
        kids = [s for s in spans if s is not feed and _inside(s, feed)]
        assert [k[2] for k in kids if k[2] == "train.draw"] == \
            ["train.draw"] * rounds
        stacks = [k for k in kids if k[2] == "train.stack"]
        assert len(stacks) == rounds + 1        # per round, then across
        assert all(k[3]["bytes"] == per_round for k in stacks[:-1])
        assert stacks[-1][3]["bytes"] == rounds * per_round
        put, = [k for k in kids if k[2] == "train.put"]
        # xs, ys and the (rounds, T) int32 iteration indices
        assert put[3]["bytes"] == rounds * per_round + rounds * T * 4


def test_serving_engine_spans(tmp_path):
    cfg = get_config("qwen2-0.5b").reduced()
    params = init_serve_params(cfg, jax.random.PRNGKey(0))
    masks = masks_mod.init_unit_masks(cfg, 4)
    eng = ContinuousEngine(cfg, params, masks, max_batch=2, cache_len=32)
    rng = np.random.default_rng(0)

    def reqs(first):
        # clients 0, 1, 0, 2, 1: repeats hit the gate cache
        return [Request(first + i, c, rng.integers(0, cfg.vocab_size, n,
                                                   dtype=np.int32), m)
                for i, (c, n, m) in enumerate([(0, 5, 3), (1, 7, 2),
                                               (0, 3, 4), (2, 6, 1),
                                               (1, 4, 2)])]

    for r in reqs(100):                      # compiles outside the trace
        eng.submit(r)
    eng.run_until_idle()
    hits0 = eng.stats.gate_hits
    sent = reqs(0)

    def serve():
        for r in sent:
            eng.submit(r)
        eng.run_until_idle()

    spans = _traced(tmp_path, serve)
    admits = _named(spans, "serve.admit")
    assert len(admits) == len(sent)
    for a in admits:
        assert list(a[3]) == ["wait_us"] and a[3]["wait_us"] >= 0
        kids = [s[2] for s in spans if s is not a and _inside(s, a)]
        assert kids == ["serve.gate", "serve.prefill", "serve.merge"]
    gates = _named(spans, "serve.gate")
    assert sum(g[3]["hit"] for g in gates) == eng.stats.gate_hits - hits0
    assert len(_named(spans, "serve.finish")) == len(sent)
    steps = _named(spans, "serve.step")
    decodes = _named(spans, "serve.decode")
    assert decodes and all(any(_inside(d, s) for s in steps)
                           for d in decodes)
    # only the admission's wait and the gate lookup carry a count
    assert {s[2] for s in spans if s[3]} == {"serve.admit", "serve.gate"}


def test_span_without_a_session_records_nothing(tmp_path):
    with span("idle.check", n=1, x=2.5):
        jnp.zeros(2).block_until_ready()
    spans = _traced(tmp_path, lambda: jnp.ones(2).block_until_ready())
    assert spans == []
    with pytest.raises(ValueError):         # the span passes errors on
        with span("idle.check"):
            raise ValueError("kept")
