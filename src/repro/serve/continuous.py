"""Continuous-batching personalized serving (vLLM/Orca mold).

One persistent decode batch of ``max_batch`` slots.  A request's life:

  submit -> FIFO queue -> ADMIT into a free slot (its prompt prefills
  alone, jitted per pow-2 length bucket, and its B=1 cache is merged
  into the slot's row of the persistent batch cache) -> it rides the
  shared jitted decode step, at ITS OWN cache position, until ITS OWN
  ``max_new_tokens`` -> the slot frees and the next queued request
  prefills into it MID-FLIGHT.

Ragged lengths are therefore the steady state, not a corner case, and
correctness comes from per-slot state rather than batch-wide padding:

* each slot feeds the decode step its own position vector entry, writes
  K/V at its own ring offset, and attends only to ``idx <= pos[slot]``
  (``models.attention.attn_decode`` per-slot path) — empty slots and
  pad keys contribute nothing;
* admission prefill right-pads to the bucket and threads
  ``last_index``/``kv_valid`` (``models.decode.prefill``), so the slot
  joins with exactly the cache it would have alone;
* per-client personalization is a per-slot GATE column (leaves
  (n_rep, B, U), ``masks.init_slot_gates``/``set_slot_gates``) updated
  at admission; client gate pytrees come from a sharded LRU
  (``serve.lru.ShardedLRU``) sized to the in-flight working set.

The decode step and the admission merge are each jitted ONCE per
engine (slot index is a traced scalar), so the steady state retraces
nothing; prefill compiles once per pow-2 prompt bucket.  Scheduling is
host-side and pure (``serve.scheduler.SlotScheduler``) — admission
order, slot exclusivity and per-request stop are property-tested
without a model.

Limits: decoder-only attention stacks (``dec.slot_serving_ok``), no
sliding window (each slot owns a full-length cache row), greedy
decode.  The FIFO ``ServeEngine`` remains the differential oracle.
"""
from __future__ import annotations

import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import masks as masks_mod
from repro.models import decode as dec
from repro.serve.engine import EngineStats, Request
from repro.serve.lru import ShardedLRU
from repro.serve.scheduler import SlotScheduler
from repro.utils.spans import span


def _bucket(n: int, cap: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return min(b, cap)


class ContinuousEngine:
    def __init__(self, cfg: ModelConfig, params, masks=None, *,
                 max_batch: int = 8, cache_len: int = 128,
                 gate_cache_size: Optional[int] = None,
                 gate_shards: int = 4, binarize_threshold: float = 0.0):
        if not dec.slot_serving_ok(cfg):
            raise ValueError(
                "ContinuousEngine needs a decoder-only attention arch "
                f"(got {cfg.name}); use ServeEngine")
        self.cfg, self.params, self.masks = cfg, params, masks
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.binarize_threshold = binarize_threshold
        self.sched = SlotScheduler(max_batch)
        self.stats = EngineStats(slot_capacity=max_batch)
        self._done: List[Request] = []
        if masks is not None:
            # properly sized: every in-flight slot's client plus rotation
            # headroom must fit, or steady traffic thrashes the cache
            cap = gate_cache_size or max(4 * max_batch, 16)
            if cap < max_batch:
                raise ValueError(
                    f"gate_cache_size {cap} < max_batch {max_batch}: "
                    "in-flight clients would evict each other")
            self._gate_lru = ShardedLRU(cap, n_shards=gate_shards)
        else:
            self._gate_lru = None

        # persistent device state
        self._cache = dec.init_cache(cfg, max_batch, cache_len)
        self._tok = jnp.zeros((max_batch, 1), jnp.int32)
        self._outbuf = jnp.zeros((max_batch, cache_len), jnp.int32)
        self._gates = masks_mod.init_slot_gates(masks, max_batch) \
            if masks is not None else None

        self._decode = jax.jit(self._decode_fn)
        self._admit_dev = jax.jit(self._admit_fn)
        self._prefills = {}

    # ------------------------------------------------------------------
    # jitted device ops
    # ------------------------------------------------------------------
    def _decode_fn(self, params, cache, tok, pos, gates, outbuf, gen_idx):
        lg, cache = dec.decode_step(self.cfg, params, tok, cache, pos,
                                    gates=gates)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        outbuf = outbuf.at[jnp.arange(tok.shape[0]), gen_idx].set(tok[:, 0])
        return tok, cache, outbuf

    def _admit_fn(self, cache, tok, outbuf, gates, slot, one_cache,
                  first_tok, client_gates):
        cache = dec.merge_slot_cache(cache, one_cache, slot)
        tok = jax.lax.dynamic_update_slice(tok, first_tok, (slot, 0))
        outbuf = jax.lax.dynamic_update_slice(outbuf, first_tok, (slot, 0))
        if gates is not None:
            gates = masks_mod.set_slot_gates(gates, slot, client_gates)
        return cache, tok, outbuf, gates

    def _prefill_for(self, bucket: int):
        """One jitted B=1 prefill per pow-2 prompt bucket."""
        fn = self._prefills.get(bucket)
        if fn is None:
            def prefill(params, prompt, last_index, gates):
                lg, cache = dec.prefill(self.cfg, params, prompt, None,
                                        gates=gates,
                                        cache_len=self.cache_len,
                                        last_index=last_index)
                return jnp.argmax(lg, axis=-1).astype(jnp.int32), cache
            fn = self._prefills[bucket] = jax.jit(prefill)
        return fn

    # ------------------------------------------------------------------
    def _gates_for(self, client_id: int):
        def build():
            g = masks_mod.gates_for_client(self.masks, client_id)
            if self.binarize_threshold > 0:
                g = masks_mod.binarize(g, self.binarize_threshold)
            return g
        with span("serve.gate", hit=int(client_id in self._gate_lru)):
            g = self._gate_lru.get_or_add(client_id, build)
        self.stats.gate_hits = self._gate_lru.hits
        self.stats.gate_misses = self._gate_lru.misses
        return g

    def submit(self, req: Request):
        L, budget = len(req.prompt), req.max_new_tokens
        if budget < 1:
            raise ValueError(f"request {req.req_id}: max_new_tokens < 1")
        if L + budget > self.cache_len:
            raise ValueError(
                f"request {req.req_id}: prompt {L} + budget {budget} "
                f"exceeds cache_len {self.cache_len}")
        req.t_submit = req.t_submit or time.time()
        self.sched.submit(req)

    # ------------------------------------------------------------------
    def _do_admit(self, slot: int, req: Request, now: float):
        L = len(req.prompt)
        b = _bucket(L, self.cache_len)
        wait_us = round((time.time() - req.t_submit) * 1e6)
        with span("serve.admit", wait_us=wait_us):
            prompt = np.zeros((1, b), np.int32)
            prompt[0, :L] = req.prompt
            gates_c = self._gates_for(req.client_id) \
                if self.masks is not None else None
            with span("serve.prefill"):
                first_tok, one_cache = self._prefill_for(b)(
                    self.params, jnp.asarray(prompt),
                    jnp.asarray([L - 1], jnp.int32), gates_c)
            with span("serve.merge"):
                self._cache, self._tok, self._outbuf, self._gates = \
                    self._admit_dev(self._cache, self._tok, self._outbuf,
                                    self._gates, jnp.asarray(slot, jnp.int32),
                                    one_cache, first_tok, gates_c)
        req.t_admit = now
        self.stats.tokens += 1          # prefill produced its first token

    def _finish(self, slot: int, req: Request):
        with span("serve.finish"):
            row = np.asarray(self._outbuf[slot, : req.max_new_tokens])
        req.output = row                 # forces the completing step
        req.t_done = time.time()
        req.latency_s = req.t_done - req.t_admit
        self.stats.requests += 1
        self.stats.completed += req.max_new_tokens
        self._done.append(req)

    def step(self) -> bool:
        """Admit into free slots, then one decode step for the whole
        batch.  Returns False when there is nothing in flight (caller
        may sleep / feed more traffic)."""
        with span("serve.step"):
            return self._step()

    def _step(self) -> bool:
        progress = False
        while True:     # admission chains: a budget-1 request frees its
            now = time.time()            # slot before any decode step
            admitted = self.sched.admit()
            for slot, req in admitted:
                self._do_admit(slot, req, now)
            completed = self.sched.pop_completed()
            for slot, req in completed:
                self._finish(slot, req)
            progress = progress or bool(admitted or completed)
            if not admitted and not completed:
                break

        act = self.sched.active()
        if not act:
            return progress
        pos = np.zeros(self.max_batch, np.int32)
        gen_idx = np.full(self.max_batch, self.cache_len - 1, np.int32)
        for i in act:
            s = self.sched.slots[i]
            pos[i] = s.pos               # free slots park at 0 / last col:
            gen_idx[i] = s.gen           # their rows are never read
        with span("serve.decode"):
            self._tok, self._cache, self._outbuf = self._decode(
                self.params, self._cache, self._tok, jnp.asarray(pos),
                self._gates, self._outbuf, jnp.asarray(gen_idx))
        n = self.sched.note_step()
        self.stats.decode_steps += 1
        self.stats.slot_steps += n
        self.stats.tokens += n
        for slot, req in self.sched.pop_completed():
            self._finish(slot, req)
        return True

    def run_until_idle(self) -> List[Request]:
        """Drain the queue; returns requests in completion order."""
        t0 = time.time()
        self._done = []
        while not self.sched.idle():
            self.step()
        self.stats.wall_s += time.time() - t0
        return self._done
