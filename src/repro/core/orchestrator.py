"""UCB client-selection orchestrator (AdaSplit §3.2, eq. 6).

A_i = l_i / s_i + sqrt(2 log T / s_i)
  l_i = sum_t gamma^(T-1-t) * L_i^t     (discounted server losses)
  s_i = sum_t gamma^(T-1-t) * S_i^t     (discounted selection flags)

Unselected clients decay their loss estimate:
  L_i^t = (L_i^{t-1} + L_i^{t-2}) / 2,  with L_i init to 100 at t=0,1.

Two faces over ONE implementation of the math:

* **Functional / on-device** — ``ucb_init`` builds a small state pytree
  and ``ucb_advantage`` / ``ucb_select`` / ``ucb_update`` /
  ``ucb_new_round`` are pure jittable functions over it.  The
  discounted sums are maintained *incrementally* (``l <- gamma*l + L``)
  so the state is O(N) regardless of history length, which is what lets
  selection live inside the round ``lax.scan`` (core/adasplit.py) and
  inside the LM train step (launch/steps.py) with no host sync.
  Tie-breaking uses keyed jitter (``jax.random.uniform`` in [0, 1e-9))
  so selection is a pure function of (state, key).  Under cohort
  sharding (``shard_clients=True``) the (N,)-leaf state rides the scan
  SHARDED on the mesh's ``data`` axis: updates are elementwise (each
  shard touches only its own client slice) and selection splits into a
  local ``ucb_advantage`` + all-gather + replicated
  ``ucb_select_from_advantage`` — bit-identical to the single-device
  top-k.  ``ingest_round`` / ``ingest_epoch`` receive the scan's final
  state as a (possibly mesh-sharded) global array and adopt it
  verbatim; host history replay is device-layout-agnostic.

* **Host class** — :class:`Orchestrator` is a thin wrapper over the
  same functions (it literally calls them), kept for the eager
  reference paths, benchmarks and tests.  It additionally mirrors the
  full L/S histories as (N, T) arrays for introspection; ``advantage``
  over that history is vectorized (one matrix-vector product, not the
  former O(N*T) Python loop) and is used only as a cross-check — live
  decisions come from the incremental state, so the host and device
  paths pick bit-identical selections given the same key schedule.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

INIT_LOSS = 100.0
# Tie-break jitter, RELATIVE to the advantage magnitude: must survive
# f32 rounding when added to advantages of ~1e2 (an absolute 1e-9 would
# be absorbed — f32 ULP at 100 is ~7.6e-6), so ~2-3 ULPs: wide enough
# to break representational ties, narrow enough that only sub-ULP-scale
# advantage gaps can be reordered.
_JITTER = 2e-7


# ---------------------------------------------------------------------------
# functional (on-device) orchestrator
# ---------------------------------------------------------------------------


def ucb_init(n: int, *, gamma: float = 0.87,
             init_loss: float = INIT_LOSS) -> dict:
    """O(N) selection state: discounted sums + last two losses.

    Equivalent to histories L=[init, init], S=[1, 1] per client (T=2):
    the discounted sums carry weight ``gamma`` on the older entry and 1
    on the newer.
    """
    g = jnp.float32(gamma)
    return {
        "l_disc": jnp.full((n,), init_loss, jnp.float32) * (1.0 + g),
        "s_disc": jnp.full((n,), 1.0, jnp.float32) * (1.0 + g),
        "last": jnp.full((n,), init_loss, jnp.float32),
        "prev": jnp.full((n,), init_loss, jnp.float32),
        "t": jnp.asarray(2, jnp.int32),
    }


def ucb_advantage(state: dict) -> jnp.ndarray:
    """Eq. 6 advantage per client, (N,) float32."""
    s = jnp.maximum(state["s_disc"], 1e-8)
    t = jnp.maximum(state["t"], 2).astype(jnp.float32)
    return state["l_disc"] / s + jnp.sqrt(2.0 * jnp.log(t) / s)


def ucb_select_from_advantage(a: jnp.ndarray, k: int, key) -> jnp.ndarray:
    """Top-k client ids from a FULL (N,) advantage vector, sorted
    ascending; ties broken by keyed jitter.  This is the replicated half
    of selection under cohort sharding: each shard computes
    ``ucb_advantage`` on its local (N/ndev,) state slice, all-gathers
    the per-shard advantages back to (N,), and runs this top-k
    replicated — the gathered vector is elementwise identical to the
    single-device ``ucb_advantage``, so selections stay bit-identical
    across device counts."""
    scale = _JITTER * (1.0 + jnp.max(jnp.abs(a)))
    jitter = jax.random.uniform(key, a.shape, jnp.float32, 0.0, 1.0)
    _, idx = jax.lax.top_k(a + jitter * scale, k)
    return jnp.sort(idx)


def ucb_select(state: dict, k: int, key) -> jnp.ndarray:
    """Top-k client ids by advantage, sorted ascending; ties broken by
    keyed jitter.  Pure: same (state, key) -> same selection, on host
    or inside a scan."""
    return ucb_select_from_advantage(ucb_advantage(state), k, key)


def ucb_update(state: dict, sel_mask, losses, *, gamma: float) -> dict:
    """Append one iteration.

    sel_mask: (N,) 0/1 selection flags; losses: (N,) server loss, only
    read where ``sel_mask`` is 1 (unselected clients decay:
    ``(last + prev) / 2``).
    """
    sel = sel_mask.astype(jnp.float32)
    decayed = (state["last"] + state["prev"]) / 2.0
    new_l = jnp.where(sel > 0, losses.astype(jnp.float32), decayed)
    return {
        "l_disc": gamma * state["l_disc"] + new_l,
        "s_disc": gamma * state["s_disc"] + sel,
        "last": new_l,
        "prev": state["last"],
        "t": state["t"] + 1,
    }


def ucb_new_round(state: dict, *, gamma: float) -> dict:
    """Reset per-round history to L=[last, last], S=[1, 1] (T=2)."""
    last = state["last"]
    ones = jnp.ones_like(state["s_disc"])
    return {
        "l_disc": last * (1.0 + gamma),
        "s_disc": ones * (1.0 + gamma),
        "last": last,
        # its own buffer: the scan drivers donate this state, and one
        # buffer donated twice is an error
        "prev": jnp.copy(last),
        "t": jnp.asarray(2, jnp.int32),
    }


def ucb_update_selected(state: dict, idx, losses, *, n: int,
                        gamma: float) -> dict:
    """:func:`ucb_update` from a (k,) selection + per-selected losses:
    scatters them into the dense (N,) mask/loss vectors exactly as the
    fused round iteration does (``zeros.at[idx].set``), so the streamed
    driver's per-iteration bandit update is the same program as the
    in-scan one."""
    sel = jnp.zeros((n,), jnp.float32).at[idx].set(1.0)
    dense = jnp.zeros((n,), jnp.float32).at[idx].set(
        losses.astype(jnp.float32))
    return ucb_update(state, sel, dense, gamma=gamma)


@functools.partial(jax.jit, static_argnames=("k",))
def _select_jit(state, k, key):
    return ucb_select(state, k, key)


@functools.partial(jax.jit, static_argnames=("n", "gamma"))
def _update_selected_jit(state, idx, losses, n, gamma):
    return ucb_update_selected(state, idx, losses, n=n, gamma=gamma)


@functools.partial(jax.jit, static_argnames=("gamma",))
def _update_jit(state, sel_mask, losses, gamma):
    return ucb_update(state, sel_mask, losses, gamma=gamma)


# ---------------------------------------------------------------------------
# host wrapper (eager reference paths, benchmarks, introspection)
# ---------------------------------------------------------------------------


class Orchestrator:
    """Thin host wrapper over the functional UCB math.

    Live decisions (``select``/``update``/``new_round``) call the pure
    functions on the device state; ``self.L`` / ``self.S`` mirror the
    full per-round histories as (N, T) float arrays (row ``i`` indexes
    like the former list-of-lists: ``o.L[i][-1]`` etc.).
    """

    def __init__(self, n_clients: int, eta: float, gamma: float = 0.87,
                 init_loss: float = INIT_LOSS, seed: int = 0):
        self.n = n_clients
        self.k = max(1, int(round(eta * n_clients)))
        self.gamma = float(gamma)
        self.init_loss = float(init_loss)
        self.state = ucb_init(n_clients, gamma=self.gamma,
                              init_loss=init_loss)
        self.L = np.full((n_clients, 2), init_loss, np.float64)
        self.S = np.ones((n_clients, 2), np.float64)
        self._base_key = jax.random.PRNGKey(seed)
        self._n_selects = 0

    # -- key schedule shared with the round scan ----------------------
    def select_key(self, counter: int):
        return jax.random.fold_in(self._base_key, counter)

    def select_on(self, state: dict, counter: int):
        """Selection for an explicit DEVICE state at key-schedule
        position ``counter`` WITHOUT advancing the host counter: the
        streamed driver resolves each iteration's selection ahead of
        staging its cohort rows (the round boundary hoists select before
        the gather) and ``ingest_round`` later advances ``_n_selects``
        for the whole round in one go."""
        return _select_jit(state, self.k, self.select_key(counter))

    def update_on(self, state: dict, idx, losses):
        """Streamed counterpart of :meth:`update` on an explicit device
        state: scatter the (k,) selection + losses into the dense bandit
        update (history replay happens later via ``ingest_round``)."""
        return _update_selected_jit(state, idx, losses, self.n,
                                    self.gamma)

    # ------------------------------------------------------------------
    def advantage(self) -> np.ndarray:
        """Eq. 6 from the *full history* (vectorized): one discount
        matvec instead of the former per-client Python loop.  Agrees
        with the incremental state to fp tolerance — a cross-check, not
        the decision path."""
        T = self.L.shape[1]
        disc = self.gamma ** (T - 1 - np.arange(T))
        l = self.L @ disc
        s = np.maximum(self.S @ disc, 1e-8)
        return l / s + np.sqrt(2.0 * np.log(max(T, 2)) / s)

    def select(self) -> np.ndarray:
        """Top-eta clients by advantage (ties broken by keyed jitter)."""
        key = self.select_key(self._n_selects)
        self._n_selects += 1
        return np.asarray(_select_jit(self.state, self.k, key))

    def update(self, selected: Sequence[int], losses: Sequence[float]):
        """losses: server loss per *selected* client this iteration."""
        sel_idx = np.asarray(selected, np.int32)
        mask = np.zeros((self.n,), np.float32)
        mask[sel_idx] = 1.0
        dense = np.zeros((self.n,), np.float32)
        dense[sel_idx] = np.asarray(losses, np.float32)
        self.state = _update_jit(self.state, jnp.asarray(mask),
                                 jnp.asarray(dense), self.gamma)
        self._append_history(mask, dense)

    def _append_history(self, mask, dense):
        decayed = (self.L[:, -1] + self.L[:, -2]) / 2.0
        new_l = np.where(mask > 0, dense, decayed)
        self.L = np.column_stack([self.L, new_l])
        self.S = np.column_stack([self.S, mask.astype(np.float64)])

    def new_round(self):
        """Reset per-round histories (T is iterations in the round)."""
        self.state = ucb_new_round(self.state, gamma=self.gamma)
        self._reset_round_history()

    def _reset_round_history(self):
        """The host-history half of ``new_round``: L=[last, last],
        S=[1, 1] — kept separate so epoch ingestion can replay in-graph
        ``ucb_new_round`` boundaries without touching the device state."""
        last = self.L[:, -1]
        self.L = np.column_stack([last, last])
        self.S = np.ones((self.n, 2), np.float64)

    # -- round/epoch-scan interop -------------------------------------
    def ingest_round(self, sel_idx, losses, state=None):
        """Absorb a whole round computed on-device.

        sel_idx: (T, k) int selections; losses: (T, k) per-selected CE.
        ``state`` (the scan's final UCB state) is adopted verbatim when
        given, so subsequent eager selections continue bit-identically;
        histories are replayed on the host for introspection.
        """
        sel_idx = np.asarray(sel_idx)
        losses = np.asarray(losses)
        for t in range(sel_idx.shape[0]):
            mask = np.zeros((self.n,), np.float32)
            mask[sel_idx[t]] = 1.0
            dense = np.zeros((self.n,), np.float32)
            dense[sel_idx[t]] = losses[t]
            self._append_history(mask, dense)
            if state is None:
                self.state = _update_jit(self.state, jnp.asarray(mask),
                                         jnp.asarray(dense), self.gamma)
        if state is not None:
            self.state = state
        self._n_selects += sel_idx.shape[0]

    def ingest_epoch(self, sel_idx, losses, *, state, n_rounds=None):
        """Absorb a whole EPOCH — R rounds computed in one (possibly
        chunked) device-resident dispatch, each round opened by an
        in-graph ``ucb_new_round`` at the scan's round boundary.

        Equivalent to R x (``new_round()``; ``ingest_round(...)``) with
        the epoch scan's final UCB state adopted once.  sel_idx /
        losses: (R, T, k), or None for a local-phase epoch (no
        selections; pass ``n_rounds``) where only the round-boundary
        history resets and the final state apply.
        """
        if sel_idx is None:
            assert n_rounds is not None
            for _ in range(n_rounds):
                self._reset_round_history()
            self.state = state
            return
        sel_idx = np.asarray(sel_idx)
        losses = np.asarray(losses)
        for r in range(sel_idx.shape[0]):
            self._reset_round_history()
            self.ingest_round(sel_idx[r], losses[r], state=state)
