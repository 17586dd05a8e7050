"""The AdaSplit training protocol (paper §3) — classification form, as
benchmarked in the paper (LeNet backbone, N clients, R rounds).

Faithful elements:
  * two-phase schedule: local phase for the first kappa*R rounds (zero
    client<->server traffic), then the global phase;
  * client models train ONLY with the local supervised NT-Xent loss
    (eq. 5) — no server gradient (P_si = 0) unless the Table-5 ablation
    flag ``server_grad_to_client`` is set;
  * UCB orchestrator (eq. 6) selects eta*N clients per global iteration;
  * server trains with CE + lambda*L1(m_i), each client updating only
    its masked partition of M^s (eq. 7-8) — per-scalar masks (paper) or
    structured per-unit masks (scale adaptation, DESIGN.md §3);
  * bandwidth / compute metering per eq. 1-2, C3-Score at the end.

Dispatch hierarchy (iteration -> round -> epoch) and residency
--------------------------------------------------------------
The trainer is a ladder of reference implementations, each level
fusing one more layer of the protocol's control plane into the
compiled graph.  Every rung reproduces the rung below it —
selections and meter totals bit-for-bit — so any level can serve as
the differential oracle for the one above.  ORTHOGONALLY to the
ladder, per-client state residency is a two-position switch:
**resident** (the default: all C clients' params / Adam moments /
masks live on device as stacked ``(C, ...)`` leaves — O(C) device
memory) or **streamed** (``streamed=True``: the same state lives in a
host- or disk-backed ``core/client_store.py`` and the device holds
only the O(chunk) rows in flight plus the round's O(S) selected
cohort; see rung 5).  The ladder rungs:

1. **Iteration-resident** (``round_scan=False``, the eager reference):
   one dispatch per protocol step — client step, host-side UCB
   ``select``, batched global step, host ``update`` — with a host sync
   per global iteration.
2. **Round-resident** (``round_scan=True``): a whole round under one
   jitted ``lax.scan`` whose step is the fused ``_round_iteration``

       client-step -> UCB select -> global-step -> UCB update

   with NO host round-trip in between.  Selection is the pure
   functional orchestrator (``core.orchestrator.ucb_*``): the
   (N,)-state UCB pytree rides in the scan carry next to the stacked
   client/server/mask/opt pytrees (all donated, so XLA updates them in
   place), and ``top_k`` with keyed jitter picks the eta*N
   clients in-graph.  The round's data is staged once as a
   (T, C, B, ...) device array; per-iteration CE losses, payload nnz
   fractions and selection indices come back as stacked (T, k)
   accumulators billed after ONE ``device_get`` per round
   (``Meter.ingest_round`` / ``Orchestrator.ingest_round``).
3. **Epoch-resident** (``epoch_scan=True``): the round boundary itself
   moves in-graph.  Consecutive same-phase rounds (cut at eval points)
   run under a rolled OUTER ``lax.scan`` whose body applies
   ``ucb_new_round`` at the boundary and then runs the inner iteration
   scan — R x T iterations per dispatch, zero host round-trips, ONE
   ``device_get`` per epoch (``Meter.ingest_epoch`` /
   ``Orchestrator.ingest_epoch``, bit-identical to R sequential
   ``ingest_round`` calls).  Because staging a whole epoch as
   (R, T, C, B, ...) can blow device memory, ``epoch_chunk_rounds``
   splits the epoch into round-chunks staged through a two-slot device
   ring — chunk k+1's async ``device_put`` overlaps the scan over
   chunk k; chunk=1 degenerates to per-round dispatch, chunk=R is the
   fully device-resident fast path.  The outer scan stays ROLLED on
   every backend: XLA compiles the round body once, which keeps the
   epoch bit-identical to the per-round program (unrolling R copies
   lets fusion reach across round boundaries and perturbs the last
   float bit) and is also faster on CPU than an R-fold unrolled
   program thrashing cache.
4. **Cohort-sharded** (``shard_clients=True``, orthogonal to the
   round/epoch rungs): the stacked client axis C is partitioned across
   the mesh's ``data`` axis with ``shard_map`` — C/ndev clients per
   device, each running ITS OWN slice of the vmapped client step, the
   batched-GEMM conv panels, the per-client Adam moments, masks and
   the (N,)-leaf UCB state.  The protocol's control plane stays
   bit-identical to the single-device run by construction:

   * selection = local ``ucb_advantage`` on the shard's state slice,
     one (N,)-float all-gather, then a REPLICATED top-k
     (``ucb_select_from_advantage``) — the gathered advantage vector
     is elementwise identical to the 1-device one;
   * the global/server step runs REPLICATED on every device over the
     all-gathered selected activations / masks / labels (k selected
     clients, exactly the arrays the split protocol transmits anyway),
     so the server params, mask updates and per-client CE losses are
     computed by the SAME reduction-order program as on one device —
     no cross-shard psum touches the training math;
   * each shard then scatters the selected rows it owns back into its
     local slice (``masks_mod.scatter_clients_shard``) and applies the
     elementwise ``ucb_update`` to its local UCB slice.

   The all-gather traffic (advantages + selected-cohort payloads) is
   billed to the NEW ``Meter.interconnect_bytes`` channel — eq. 2
   protocol bandwidth stays device-layout-invariant.  C must divide by
   the mesh's data size; otherwise the trainer warns and falls back to
   the replicated single-device path (the same must-always-lower
   policy as ``sharding/rules.py``).
5. **Host-streamed** (``streamed=True``, orthogonal to rungs 1-3 and
   composable with 4): per-client state moves off-device into a
   ``core/client_store.py`` backend (``store_backend="host"`` pins it
   in host numpy; ``"disk"`` spills to a memmappable
   ``checkpoint/io.py`` directory checkpoint) and each round runs as
   two passes that COMMUTE exactly with the resident interleaving —
   the client steps never read anything the global steps write (the
   ``server_grad_to_client`` ablation breaks that and falls back to
   resident with a warning):

   * **client pass** — all C clients stream through the device in
     ``stream_chunk``-row cohorts via the PR-4 two-slot staging ring
     (chunk k+1's H2D ``device_put`` + store gather overlap chunk k's
     jitted T-iteration scan), updated params/moments scattering back
     to the store as each chunk drains; split activations spill to a
     host buffer.  Device residency: two chunks of client/proj/opt
     rows, never O(C).
   * **global pass** — per iteration, selection resolves FIRST on the
     device-resident O(N) UCB state (``Orchestrator.select_on``), then
     only the S selected clients' mask/opt rows + spilled activations
     stage in, run the SAME jitted ``_global_step`` as the eager rung,
     and scatter back; ``Orchestrator.update_on`` applies the identical
     dense bandit update.  Device residency: O(S) rows.

   The UCB state and selection math stay device-resident for the full
   population throughout — only the O(C) training state streams.
   Billing is unchanged on the protocol channels (``ingest_round`` /
   ``ingest_epoch`` with identical arguments — bandwidth / FLOP totals
   are residency-invariant and differentially pinned) while the
   store's gather/scatter + activation-spill traffic lands on the
   ``Meter.host_device_bytes`` channel that all rungs use for staging
   H2D billing.  Composed with ``shard_clients``, each streamed chunk
   is ``NamedSharding``-placed with its cohort axis on ``data`` (each
   shard computes only its owned rows; no collectives, so
   interconnect bytes stay 0) and the global pass runs replicated.

Within one iteration the global phase is the PR-1 batched step: the
selected S = eta*N clients run as one (S*B)-flattened forward with
per-example gates, the server gradient mean-combined into a single
``adam_update`` on M^s and per-client mask/opt updates scattered back
in one ``.at[idx].set``.  The same S*B segment-reduction form now also
covers the Table-5 ``server_grad_to_client`` joint step
(``flat_joint=True``; the earlier vmap-per-client form is kept as the
``flat_joint=False`` reference).  ``serialize_server_updates=True``
keeps an exact-sequential ``lax.scan`` over the selection inside the
step (reproduces the seed's per-client loop bit-for-bit);
``global_batch=False`` retains the original per-client host loop, and
``round_scan=False`` the per-iteration eager driver — both as reference
implementations for the differential tests and benchmarks
(``benchmarks/round_scan.py``, ``benchmarks/global_phase.py``).
``fused_mask_adam`` routes the per-client mask updates through the
fused Pallas masked-Adam kernel (``kernels/masked_adam``);
``fused_server_adam`` does the same for the server optimizer step.
Both default to ``None`` = backend-aware: ON when
``jax.default_backend() == "tpu"`` (where the kernel lowers natively),
OFF elsewhere; an explicit False always wins, and an explicit True on
a backend that cannot lower the kernel raises (``_fused_default``).

``batched_conv=True`` (default) lowers every per-client conv in the hot
path — the vmapped client step, the joint step's client part, the
per-scalar server vmap, and ``_eval_all`` — through the im2col
batched-GEMM form (``kernels/client_conv``): one
``(C, B*H*W, K*K*Cin) @ (C, K*K*Cin, Cout)`` dispatch in forward AND
backward, replacing the feature-group conv XLA:CPU executes
group-serially (its transposed backward is ~70x slower than the GEMM
form at C=32).  ``batched_conv=False`` keeps the
``lax.conv_general_dilated`` lowering as the reference path.
``fused_epilogue=True`` additionally hands each block's bias+ReLU to
the conv kernel's epilogue — fused into the Pallas GEMM writeback on
TPU, bit-identical plain XLA ops elsewhere (opt-in until benchmarked
natively).

The LM/pod-scale variant of the same protocol lives in
``repro.launch.train`` (batched cohorts on the device mesh, with the
same in-graph orchestrator via ``launch.steps.build_ucb_train_step``).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import masks as masks_mod
from repro.core.accounting import (Meter, lenet_flops_per_example,
                                   split_payload_bytes)
from repro.core.c3 import c3_score
from repro.core.client_store import make_store
from repro.core.losses import (accuracy, cross_entropy, l1_penalty,
                               ntxent_supervised)
from repro.core.orchestrator import (Orchestrator, ucb_advantage,
                                     ucb_new_round, ucb_select,
                                     ucb_select_from_advantage, ucb_update)
from repro.kernels.client_conv import client_proj
from repro.models import lenet
from repro.optim.adam import adam_init, adam_update
from repro.sharding.rules import (MeshAxes, cohort_pspecs,
                                  staged_cohort_spec)
from repro.utils.spans import span


@dataclass
class AdaSplitHParams:
    rounds: int = 20
    kappa: float = 0.6          # local-phase fraction
    eta: float = 0.6            # selected-client fraction
    gamma: float = 0.87         # UCB discount
    lam: float = 1e-5           # mask L1 coefficient
    tau: float = 0.07           # NT-Xent temperature
    lr: float = 1e-3
    batch_size: int = 32
    proj_dim: int = 64
    mask_mode: str = "per_unit"     # "per_unit" | "per_scalar"
    act_l1: float = 0.0             # beta: split-activation sparsification
    act_threshold: float = 1e-3     # payload nnz threshold
    server_grad_to_client: bool = False  # Table-5 ablation
    global_batch: bool = True       # batched global phase (False = seed loop)
    serialize_server_updates: bool = False  # exact-sequential scan in one jit
    round_scan: bool = True         # whole round under one jitted lax.scan
    epoch_scan: bool = False        # multiple rounds per dispatch (epoch-
                                    # resident; in-graph ucb_new_round)
    epoch_chunk_rounds: int = 0     # rounds per staged dispatch chunk
                                    # (0 = whole epoch device-resident;
                                    # 1 degenerates to per-round dispatch)
    flat_joint: bool = True         # S*B-flattened joint step (vs vmap ref)
    fused_mask_adam: Optional[bool] = None    # Pallas fused mask update;
    fused_server_adam: Optional[bool] = None  # Pallas fused server Adam;
                                    # None = backend-aware default (auto-
                                    # on on TPU, off elsewhere)
    batched_conv: bool = True       # im2col batched-GEMM convs (False = ref)
    fused_epilogue: bool = False    # bias+ReLU in the Pallas GEMM epilogue
                                    # (TPU; identical XLA ops elsewhere)
    shard_clients: bool = False     # shard_map the stacked client axis C
                                    # over the mesh's `data` axis (falls
                                    # back to 1-device when C % ndev != 0)
    streamed: bool = False          # host/disk-backed client store:
                                    # device holds O(chunk)+O(S) client
                                    # rows instead of O(C)
    store_backend: str = "host"     # "host" (pinned numpy) | "disk"
                                    # (checkpoint-spill memmaps)
    store_dir: Optional[str] = None  # DiskStore directory (None = tmp)
    stream_chunk: int = 0           # client rows per streamed device
                                    # cohort (0 = auto)
    seed: int = 0


def _fused_default(flag: Optional[bool], on_tpu: bool) -> bool:
    """Backend-aware default for the fused Pallas Adam kernels: ``None``
    resolves to on iff running on TPU (where the kernels lower
    natively); an explicit False always wins, and an explicit True on
    a backend that cannot lower the kernel is an error — never a
    silent plain-Adam run."""
    if flag is None:
        return on_tpu
    if flag and not on_tpu:
        raise ValueError(
            "fused Pallas Adam requested on the "
            f"{jax.default_backend()!r} backend, which cannot lower it "
            "natively; pass None (backend default) or False")
    return bool(flag)


def _proj_init(key, in_dim, proj_dim):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (in_dim, 128)) * (1 / np.sqrt(in_dim)),
            "b1": jnp.zeros((128,)),
            "w2": jax.random.normal(k2, (128, proj_dim)) * (1 / np.sqrt(128))}


def _proj_apply(p, acts):
    h = acts.reshape(acts.shape[0], -1).astype(jnp.float32)
    return client_proj(p, h)


class AdaSplitTrainer:
    def __init__(self, cfg: ModelConfig, hp: AdaSplitHParams, clients,
                 *, mesh=None):
        self.cfg, self.hp, self.clients = cfg, hp, clients
        self.n = len(clients)
        key = jax.random.PRNGKey(hp.seed)
        kc, ks, kp = jax.random.split(key, 3)

        self.orch = Orchestrator(self.n, hp.eta, hp.gamma, seed=hp.seed)
        self._streamed = hp.streamed
        if self._streamed and hp.server_grad_to_client:
            warnings.warn(
                "streamed=True is incompatible with the joint "
                "server_grad_to_client step (it updates client params "
                "mid-round, so the client/global passes no longer "
                "commute); falling back to the resident path")
            self._streamed = False
        if self._streamed and not hp.global_batch:
            warnings.warn("streamed=True requires the batched global "
                          "phase (global_batch=True); falling back to "
                          "the resident path")
            self._streamed = False
        self._stream_chunk = min(self.n, hp.stream_chunk
                                 or max(32, self.orch.k))

        acts_dim = self._acts_dim()
        self.server_params = lenet.init_server_params(cfg, ks)
        self.s_opt = adam_init(self.server_params)
        self.store = None
        if self._streamed:
            # O(chunk) device residency from step zero: init streams
            # through the store chunk-wise (vmapped fold_in init is
            # bit-identical to the resident per-client stack)
            self._init_streamed_store(kc, kp, acts_dim)
            self.client_params = self.proj_params = None
            self.masks = self.c_opt = self.m_opt = None
        else:
            # per-client models (stacked leading C) + projection heads
            cps = [lenet.init_client_params(cfg, jax.random.fold_in(kc, i))
                   for i in range(self.n)]
            self.client_params = jax.tree.map(lambda *x: jnp.stack(x), *cps)
            pps = [_proj_init(jax.random.fold_in(kp, i), acts_dim,
                              hp.proj_dim)
                   for i in range(self.n)]
            self.proj_params = jax.tree.map(lambda *x: jnp.stack(x), *pps)

            if hp.mask_mode == "per_scalar":
                self.masks = masks_mod.init_scalar_masks(self.server_params,
                                                         self.n)
            else:
                self.masks = masks_mod.init_lenet_unit_masks(cfg, self.n)

            # per-client Adam states carry a per-client step vector so
            # they can be sliced/vmapped uniformly
            self.c_opt = adam_init({"c": self.client_params,
                                    "p": self.proj_params})
            self.c_opt["step"] = jnp.zeros((self.n,), jnp.int32)
            self.m_opt = adam_init(self.masks)
            self.m_opt["step"] = jnp.zeros((self.n,), jnp.int32)

        self.meter = Meter()
        self._fl_c = lenet_flops_per_example(cfg, "client")
        self._fl_s = lenet_flops_per_example(cfg, "server")
        self.history: List[Dict[str, Any]] = []
        self._rng = np.random.default_rng(hp.seed)
        self._round_fns: Dict[Any, Any] = {}
        self._mesh = self._ax = None
        self._shard = False
        if hp.shard_clients:
            self._setup_cohort_sharding(mesh)
        self._compile()

    # ------------------------------------------------------------------
    # cohort sharding: partition the stacked client axis on `data`
    # ------------------------------------------------------------------
    def _setup_cohort_sharding(self, mesh):
        """Enable ``shard_clients``: validate divisibility, build the
        carry PartitionSpec trees once (shapes are static for the
        trainer's lifetime) and place the stacked per-client state on
        the mesh.  Non-divisible cohorts warn and fall back to the
        single-device path — the scan drivers and their outputs are
        identical either way, sharding only changes layout."""
        if not (self.hp.round_scan and self.hp.global_batch):
            warnings.warn("shard_clients requires the round/epoch scan "
                          "drivers (round_scan=True, global_batch=True); "
                          "falling back to the single-device path")
            return
        from repro.launch.mesh import make_cohort_mesh
        mesh = mesh if mesh is not None else make_cohort_mesh()
        ax = MeshAxes.from_mesh(mesh)
        if ax.data_size <= 1:
            return
        if self.n % ax.data_size:
            warnings.warn(
                f"shard_clients: {self.n} clients not divisible by "
                f"data mesh size {ax.data_size}; falling back to the "
                "replicated single-device path")
            return
        self._mesh, self._ax, self._shard = mesh, ax, True
        self._n_local = self.n // ax.data_size
        if self._streamed:
            # streamed composition: no resident carries to place — each
            # streamed chunk is NamedSharding-placed per round with its
            # cohort axis on `data` (per-row-independent client pass, no
            # collectives); the global pass and UCB state stay on the
            # default device.  Chunks whose row count doesn't divide the
            # data axis stage replicated (must-always-lower fallback).
            return

        def rep(tree):
            return jax.tree.map(lambda _: P(), tree)

        def coh(tree):
            return cohort_pspecs(tree, ax, cohort_size=self.n)

        self._carry_specs = (
            coh({"c": self.client_params, "p": self.proj_params}),
            coh(self.c_opt), rep(self.server_params), rep(self.s_opt),
            coh(self.masks), coh(self.m_opt), coh(self.orch.state))
        # adopt the sharded layout for the live state
        (cp_pp, self.c_opt, self.server_params, self.s_opt, self.masks,
         self.m_opt, self.orch.state) = jax.tree.map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            self._carry(), self._carry_specs)
        self.client_params, self.proj_params = cp_pp["c"], cp_pp["p"]

    # ------------------------------------------------------------------
    # streamed residency: host/disk client store, O(chunk)+O(S) device
    # ------------------------------------------------------------------
    def _init_streamed_store(self, kc, kp, acts_dim):
        """Populate the client store chunk by chunk without ever
        materializing the (C, ...) stacked trees on device.  The
        vmapped ``fold_in`` init is bit-identical to the resident
        per-client ``jnp.stack`` (verified differentially), masks init
        to constant ones and Adam moments to zeros — so a streamed
        trainer starts from exactly the resident trainer's state."""
        hp, cfg = self.hp, self.cfg
        self.store = make_store(hp.store_backend, self.n,
                                directory=hp.store_dir)
        init_c = jax.vmap(lambda k: lenet.init_client_params(cfg, k))
        init_p = jax.vmap(lambda k: _proj_init(k, acts_dim, hp.proj_dim))
        fold = jax.vmap(lambda i: jax.random.fold_in(kc, i))
        fold_p = jax.vmap(lambda i: jax.random.fold_in(kp, i))
        chunk = self._stream_chunk
        for i0 in range(0, self.n, chunk):
            m = min(chunk, self.n - i0)
            ids = jnp.arange(i0, i0 + m)
            cp = init_c(fold(ids))
            pp = init_p(fold_p(ids))
            co = adam_init({"c": cp, "p": pp})
            co["step"] = jnp.zeros((m,), jnp.int32)
            if hp.mask_mode == "per_scalar":
                mk = masks_mod.init_scalar_masks(self.server_params, m)
            else:
                mk = masks_mod.init_lenet_unit_masks(cfg, m)
            mo = adam_init(mk)
            mo["step"] = jnp.zeros((m,), jnp.int32)
            groups = {"cp": {"c": cp, "p": pp}, "co": co,
                      "m": mk, "mo": mo}
            if i0 == 0:
                for name, tree in groups.items():
                    self.store.alloc(name, jax.tree.map(
                        lambda l: jax.ShapeDtypeStruct(
                            (self.n,) + l.shape[1:], l.dtype), tree))
            self.store.scatter(np.arange(i0, i0 + m), groups)

    def _stream_put_rows(self, tree, m):
        """Device placement for a streamed chunk of (m, ...) client-state
        rows: cohort axis on ``data`` when sharding (and m divides the
        data axis), plain transfer otherwise."""
        if self._shard and m % self._ax.data_size == 0:
            specs = cohort_pspecs(tree, self._ax, cohort_size=m)
            return jax.tree.map(
                lambda x, sp: jax.device_put(
                    x, NamedSharding(self._mesh, sp)), tree, specs)
        return jax.device_put(tree)

    def _stream_put_data(self, x, m):
        """Device placement for a streamed chunk's (T, m, B, ...) round
        data (cohort axis = dim 1)."""
        if self._shard and m % self._ax.data_size == 0:
            spec = staged_cohort_spec(self._ax, x.ndim, cohort_dim=1)
            return jax.device_put(x, NamedSharding(self._mesh, spec))
        return jax.device_put(x)

    def _put_staged(self, x, *, cohort_dim):
        """Device placement for staged (T, C, B, ...) / (R, T, C, B,
        ...) round data: cohort axis on ``data`` when sharding, plain
        transfer otherwise."""
        if not self._shard:
            return jax.device_put(x)
        spec = staged_cohort_spec(self._ax, cohort_dim + 1,
                                  cohort_dim=cohort_dim)
        return jax.device_put(x, NamedSharding(self._mesh, spec))

    def _tree_bytes(self, tree) -> int:
        return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))

    def _iteration_interconnect_bytes(self) -> float:
        """Analytic cross-device bytes for ONE sharded global
        iteration: every all-gather in the iteration body moves
        (ndev - 1) x its full array size across the mesh (ring
        convention).  Gathered per iteration: the (N,) advantages, the
        split activations + labels of ALL clients (the candidates the
        replicated global step selects from), the mask + mask-opt
        pytrees, and — on the joint ablation — the client params/opt
        and inputs.  Local-phase iterations gather nothing."""
        if not self._shard:
            return 0.0
        hp = self.hp
        full = 4 * self.n                                   # advantages
        full += 4 * self.n * hp.batch_size * int(
            np.prod(self._acts_spatial))                    # activations
        full += 4 * self.n * hp.batch_size                  # labels
        full += self._tree_bytes(self.masks)
        full += self._tree_bytes(self.m_opt)
        if hp.server_grad_to_client:
            full += self._tree_bytes(
                {"c": self.client_params, "p": self.proj_params})
            full += self._tree_bytes(self.c_opt)
            full += 4 * self.n * hp.batch_size * 3 \
                * self.cfg.image_size ** 2                  # images
        return float((self._ax.data_size - 1) * full)

    def _staging_bytes_per_round(self, T: int) -> float:
        """Analytic H2D bytes for staging one round's batches: (T, C, B)
        f32 images + int32 labels.  Billed IDENTICALLY by every dispatch
        rung (the eager driver uploads (C, B, ...) per iteration, the
        scans (T, C, B, ...) per round, the epoch ring (R, T, C, B, ...)
        per chunk — same totals), so the ``host_device_bytes`` channel
        stays rung-invariant on the resident ladder."""
        img = 4 * 3 * self.cfg.image_size ** 2
        return float(T * self.n * self.hp.batch_size * (img + 4))

    def _stream_store_bytes(self, T: int, global_phase: bool) -> float:
        """Analytic host<->device bytes for ONE streamed round's store
        traffic (on top of the data staging every rung bills):

        * client pass: every client's params/opt row crosses twice
          (gather H2D + scatter D2H) and its (T, B, ...) split
          activations spill D2H to the host buffer;
        * global pass: per iteration, the S selected clients' mask/opt
          rows cross twice and their activations + labels re-stage H2D.

        HostStore and DiskStore rows are byte-identical (bf16 disk
        views keep the itemsize), so billing is backend-invariant.
        """
        hp = self.hp
        act = 4 * int(np.prod(self._acts_spatial))
        b = 2.0 * self.store.nbytes(("cp", "co"))
        b += float(T * self.n * hp.batch_size * act)
        if global_phase:
            row = self.store.row_nbytes(("m", "mo"))
            payload = hp.batch_size * (act + 4)
            b += float(T * self.orch.k * (2 * row + payload))
        return b

    # ------------------------------------------------------------------
    def _acts_dim(self):
        x = jnp.zeros((1, self.cfg.image_size, self.cfg.image_size, 3))
        cp = lenet.init_client_params(self.cfg, jax.random.PRNGKey(0))
        a = lenet.client_forward(self.cfg, cp, x)
        self._acts_spatial = tuple(a.shape[1:])
        return int(np.prod(a.shape[1:]))

    def _compile(self):
        cfg, hp = self.cfg, self.hp
        bc = hp.batched_conv
        # conv lowering flags for every hot-path forward: the batched
        # GEMM form + (opt-in) the fused bias+ReLU kernel epilogue
        fwd_kw = dict(batched_conv=bc, fused_epilogue=hp.fused_epilogue)
        on_tpu = jax.default_backend() == "tpu"

        def gated_adam(fused: bool):
            """Adam step: the fused Pallas kernel (one HBM pass per
            leaf, native lowering) when the resolved flag is on, plain
            ``adam_update`` otherwise."""
            if fused:
                from repro.kernels.masked_adam import fused_adam_update

                def step(p, g, o):
                    return fused_adam_update(p, g, o, lr=hp.lr,
                                             interpret=False)
            else:
                def step(p, g, o):
                    return adam_update(p, g, o, lr=hp.lr)
            return step

        mask_adam = gated_adam(_fused_default(hp.fused_mask_adam, on_tpu))
        server_adam = gated_adam(_fused_default(hp.fused_server_adam,
                                                on_tpu))

        def client_loss(cp_pp, x, y):
            acts = lenet.client_forward(cfg, cp_pp["c"], x,
                                        **fwd_kw)
            q = _proj_apply(cp_pp["p"], acts)
            loss = ntxent_supervised(q, y, hp.tau)
            if hp.act_l1:
                loss = loss + hp.act_l1 * jnp.sum(jnp.abs(acts)) / acts.shape[0]
            return loss, acts

        def client_step(cp_pp, opt, x, y):
            (loss, acts), g = jax.value_and_grad(client_loss, has_aux=True)(
                cp_pp, x, y)
            new, opt = adam_update(cp_pp, g, opt, lr=hp.lr)
            return new, opt, loss, acts

        # vmapped across clients (each on its own batch) — Adam state has a
        # shared scalar step; vmap over it too (stacked below).
        self._client_step_fn = jax.vmap(client_step)
        self._client_step = jax.jit(self._client_step_fn)

        def server_loss(sp, mask_i, acts, y):
            if hp.mask_mode == "per_scalar":
                eff = masks_mod.apply_scalar_masks(sp, mask_i)
                logits, _ = lenet.server_forward(cfg, eff, acts,
                                                 **fwd_kw)
            else:
                logits, _ = lenet.server_forward(cfg, sp, acts,
                                                 gates=mask_i,
                                                 **fwd_kw)
            loss = cross_entropy(logits, y)
            return loss + hp.lam * l1_penalty(mask_i), loss

        def server_step(sp, s_opt, mask_i, m_opt_i, acts, y):
            (total, ce), g = jax.value_and_grad(server_loss, argnums=(0, 1),
                                                has_aux=True)(sp, mask_i,
                                                              acts, y)
            sp, s_opt = server_adam(sp, g[0], s_opt)
            mask_i, m_opt_i = adam_update(mask_i, g[1], m_opt_i, lr=hp.lr)
            return sp, s_opt, mask_i, m_opt_i, ce

        self._server_step = jax.jit(server_step)

        def joint_loss(cp_pp, sp, mask_i, x, y):
            """Table-5 ablation: client also receives the server CE grad."""
            acts = lenet.client_forward(cfg, cp_pp["c"], x,
                                        **fwd_kw)
            q = _proj_apply(cp_pp["p"], acts)
            lc = ntxent_supervised(q, y, hp.tau)
            if hp.mask_mode == "per_scalar":
                eff = masks_mod.apply_scalar_masks(sp, mask_i)
                logits, _ = lenet.server_forward(cfg, eff, acts,
                                                 **fwd_kw)
            else:
                logits, _ = lenet.server_forward(cfg, sp, acts,
                                                 gates=mask_i,
                                                 **fwd_kw)
            ce = cross_entropy(logits, y)
            return lc + ce + hp.lam * l1_penalty(mask_i), ce

        def joint_step(cp_pp, c_opt_i, sp, s_opt, mask_i, m_opt_i, x, y):
            (_, ce), g = jax.value_and_grad(joint_loss, argnums=(0, 1, 2),
                                            has_aux=True)(cp_pp, sp, mask_i,
                                                          x, y)
            cp_pp, c_opt_i = adam_update(cp_pp, g[0], c_opt_i, lr=hp.lr)
            sp, s_opt = server_adam(sp, g[1], s_opt)
            mask_i, m_opt_i = adam_update(mask_i, g[2], m_opt_i, lr=hp.lr)
            return cp_pp, c_opt_i, sp, s_opt, mask_i, m_opt_i, ce

        self._joint_step = jax.jit(joint_step)

        # ---- batched global phase (leading S = selected clients) -----
        def sparsify(acts_sel):
            """Returns (possibly thresholded acts, per-client nnz (S,))."""
            if not hp.act_l1:
                return acts_sel, jnp.ones((acts_sel.shape[0],), jnp.float32)
            nz = jnp.abs(acts_sel) > hp.act_threshold
            axes = tuple(range(1, acts_sel.ndim))
            fracs = jnp.mean(nz.astype(jnp.float32), axis=axes)
            return jnp.where(nz, acts_sel, 0), fracs

        def seg_ces(logits, y_flat, S):
            """Per-client mean CE from (S*B,) flattened logits."""
            logits = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, y_flat[:, None],
                                       axis=-1)[:, 0]
            return (lse - gold).reshape(S, -1).mean(axis=1)

        def flat_server_loss(sp, masks_sel, acts_flat, y_flat, seg_ids, S):
            """One (S*B)-example forward with per-example gates gathered
            by client id.  Sum-of-clients loss: grad wrt masks_sel is
            each client's own CE+L1 gradient (the gather's backward
            scatter-adds per segment), grad wrt sp is the SUM of
            per-client gradients (mean = /S outside).  Identical math to
            a vmap of ``server_loss``, but one conv at S*B batch instead
            of S convs at B — the segment-reduction form that makes the
            global phase scale with hardware batch efficiency."""
            gates = jax.tree.map(lambda l: l[seg_ids], masks_sel)
            logits, _ = lenet.server_forward(cfg, sp, acts_flat,
                                             gates=gates, **fwd_kw)
            ces = seg_ces(logits, y_flat, S)
            total = jnp.sum(ces) + hp.lam * l1_penalty(masks_sel) * S
            return total, ces

        def global_step(sp, s_opt, masks_sel, m_opt_sel, acts_sel, ys_sel):
            acts_sel, fracs = sparsify(acts_sel)
            if hp.serialize_server_updates:
                def body(carry, xs):
                    sp, s_opt = carry
                    m, mo, a, y = xs
                    sp, s_opt, m, mo, ce = server_step(sp, s_opt, m, mo, a, y)
                    return (sp, s_opt), (m, mo, ce)
                (sp, s_opt), (masks_sel, m_opt_sel, ces) = jax.lax.scan(
                    body, (sp, s_opt),
                    (masks_sel, m_opt_sel, acts_sel, ys_sel))
            elif hp.mask_mode == "per_scalar":
                # per-example scalar masks cannot share one forward
                # (each client has distinct effective weights) — vmap.
                grad_fn = jax.value_and_grad(server_loss, argnums=(0, 1),
                                             has_aux=True)
                (_, ces), g = jax.vmap(grad_fn, in_axes=(None, 0, 0, 0))(
                    sp, masks_sel, acts_sel, ys_sel)
                g_sp = jax.tree.map(lambda t: jnp.mean(t, axis=0), g[0])
                sp, s_opt = server_adam(sp, g_sp, s_opt)
                masks_sel, m_opt_sel = jax.vmap(mask_adam)(
                    masks_sel, g[1], m_opt_sel)
            else:
                S, B = acts_sel.shape[:2]
                acts_flat = acts_sel.reshape((S * B,) + acts_sel.shape[2:])
                seg_ids = jnp.repeat(jnp.arange(S), B)
                (_, ces), g = jax.value_and_grad(
                    flat_server_loss, argnums=(0, 1), has_aux=True)(
                    sp, masks_sel, acts_flat, ys_sel.reshape(-1), seg_ids,
                    S)
                g_sp = jax.tree.map(lambda t: t / S, g[0])
                sp, s_opt = server_adam(sp, g_sp, s_opt)
                masks_sel, m_opt_sel = jax.vmap(mask_adam)(
                    masks_sel, g[1], m_opt_sel)
            return sp, s_opt, masks_sel, m_opt_sel, ces, fracs

        self._global_step_fn = global_step
        self._global_step = jax.jit(global_step)

        def flat_joint_loss(cp_sel, sp, masks_sel, xs_sel, ys_sel,
                            seg_ids, S):
            """Joint (Table-5) step in the same S*B segment-reduction
            form as ``flat_server_loss``: per-client forwards stay
            vmapped (each client has its own params) but the shared
            server runs ONE flattened conv over all S*B examples.
            Sum-of-clients loss => grads wrt cp_sel / masks_sel are each
            client's own, grad wrt sp the sum (mean = /S outside) —
            identical math to the vmap of ``joint_loss``."""
            def client_part(cp_pp, x):
                acts = lenet.client_forward(cfg, cp_pp["c"], x,
                                            **fwd_kw)
                q = _proj_apply(cp_pp["p"], acts)
                return acts, q

            acts, qs = jax.vmap(client_part)(cp_sel, xs_sel)
            lcs = jax.vmap(
                lambda q, y: ntxent_supervised(q, y, hp.tau))(qs, ys_sel)
            B = xs_sel.shape[1]
            acts_flat = acts.reshape((S * B,) + acts.shape[2:])
            gates = jax.tree.map(lambda l: l[seg_ids], masks_sel)
            logits, _ = lenet.server_forward(cfg, sp, acts_flat,
                                             gates=gates, **fwd_kw)
            ces = seg_ces(logits, ys_sel.reshape(-1), S)
            total = jnp.sum(lcs) + jnp.sum(ces) \
                + hp.lam * l1_penalty(masks_sel) * S
            return total, ces

        def global_joint_step(cp_sel, c_opt_sel, sp, s_opt, masks_sel,
                              m_opt_sel, xs_sel, ys_sel, acts_sel):
            _, fracs = sparsify(acts_sel)
            if hp.serialize_server_updates:
                def body(carry, xs):
                    sp, s_opt = carry
                    cp, co, m, mo, x, y = xs
                    cp, co, sp, s_opt, m, mo, ce = joint_step(
                        cp, co, sp, s_opt, m, mo, x, y)
                    return (sp, s_opt), (cp, co, m, mo, ce)
                (sp, s_opt), (cp_sel, c_opt_sel, masks_sel, m_opt_sel,
                              ces) = jax.lax.scan(
                    body, (sp, s_opt),
                    (cp_sel, c_opt_sel, masks_sel, m_opt_sel, xs_sel,
                     ys_sel))
            elif hp.flat_joint and hp.mask_mode != "per_scalar":
                S, B = xs_sel.shape[:2]
                seg_ids = jnp.repeat(jnp.arange(S), B)
                (_, ces), g = jax.value_and_grad(
                    flat_joint_loss, argnums=(0, 1, 2), has_aux=True)(
                    cp_sel, sp, masks_sel, xs_sel, ys_sel, seg_ids, S)
                cp_sel, c_opt_sel = jax.vmap(
                    lambda c, gc, co: adam_update(c, gc, co, lr=hp.lr))(
                    cp_sel, g[0], c_opt_sel)
                g_sp = jax.tree.map(lambda t: t / S, g[1])
                sp, s_opt = server_adam(sp, g_sp, s_opt)
                masks_sel, m_opt_sel = jax.vmap(mask_adam)(
                    masks_sel, g[2], m_opt_sel)
            else:
                grad_fn = jax.value_and_grad(joint_loss, argnums=(0, 1, 2),
                                             has_aux=True)
                (_, ces), g = jax.vmap(grad_fn,
                                       in_axes=(0, None, 0, 0, 0))(
                    cp_sel, sp, masks_sel, xs_sel, ys_sel)
                cp_sel, c_opt_sel = jax.vmap(
                    lambda c, gc, co: adam_update(c, gc, co, lr=hp.lr))(
                    cp_sel, g[0], c_opt_sel)
                g_sp = jax.tree.map(lambda t: jnp.mean(t, axis=0), g[1])
                sp, s_opt = server_adam(sp, g_sp, s_opt)
                masks_sel, m_opt_sel = jax.vmap(mask_adam)(
                    masks_sel, g[2], m_opt_sel)
            return (cp_sel, c_opt_sel, sp, s_opt, masks_sel, m_opt_sel,
                    ces, fracs)

        self._global_joint_fn = global_joint_step
        self._global_joint_step = jax.jit(global_joint_step)

        def eval_client(cp, sp, mask_i, x, y):
            acts = lenet.client_forward(cfg, cp, x, **fwd_kw)
            if hp.mask_mode == "per_scalar":
                eff = masks_mod.apply_scalar_masks(sp, mask_i)
                logits, _ = lenet.server_forward(cfg, eff, acts,
                                                 **fwd_kw)
            else:
                logits, _ = lenet.server_forward(cfg, sp, acts, gates=mask_i,
                                                 **fwd_kw)
            return accuracy(logits, y)

        self._eval_client = jax.jit(eval_client)
        # all clients at once (single device round-trip per evaluate());
        # sharded, each shard evaluates its own clients — Pallas kernels
        # are not partitioned automatically
        eval_all = jax.vmap(eval_client, in_axes=(0, None, 0, 0, 0))
        if self._shard and not self._streamed:
            coh = P(self._ax.data_spec)
            eval_all = jax.shard_map(
                eval_all, mesh=self._mesh,
                in_specs=(coh, P(), coh, coh, coh), out_specs=coh,
                check_vma=False)
        self._eval_all = jax.jit(eval_all)

    # ------------------------------------------------------------------
    # device-resident round: fused iteration + lax.scan over T
    # ------------------------------------------------------------------
    def _iteration_fn(self, global_phase: bool):
        """The fused per-iteration body shared by the round and epoch
        scans: client-step -> in-graph UCB select -> global-step ->
        UCB update, carry = (params, opts, masks, bandit state).

        Under cohort sharding the same body runs INSIDE a ``shard_map``
        over the ``data`` axis: the carry trees and the staged batch
        are the shard's (C/ndev, ...) slices, selection all-gathers the
        per-shard advantages into the replicated top-k, the global step
        runs replicated over the all-gathered selected cohort, and each
        shard scatters back / ``ucb_update``s only the rows it owns —
        so the outputs (and the scan carry, viewed globally) are
        bit-identical to the unsharded body."""
        hp = self.hp
        n, k, gamma = self.n, self.orch.k, self.hp.gamma
        client_step = self._client_step_fn
        global_step = self._global_step_fn
        global_joint = self._global_joint_fn
        select_key = self.orch.select_key   # one key schedule, all paths
        sharded = self._shard
        if sharded:
            axis, nl = self._ax.data_spec, self._n_local
            assert isinstance(axis, str), axis  # 1-D cohort mesh

        def gather_full(tree):
            """Shard-local (C/ndev, ...) leaves -> global (C, ...)."""
            if not sharded:
                return tree
            return jax.tree.map(
                lambda l: jax.lax.all_gather(l, axis, axis=0, tiled=True),
                tree)

        def scatter_back(tree, idx, new):
            if not sharded:
                return masks_mod.scatter_clients(tree, idx, new)
            off = jax.lax.axis_index(axis) * nl
            return masks_mod.scatter_clients_shard(tree, idx, new,
                                                   offset=off, size=nl)

        def _round_iteration(carry, xs):
            cp_pp, c_opt, sp, s_opt, masks, m_opt, ucb = carry
            x_t, y_t, t = xs
            cp_pp, c_opt, _, acts = client_step(cp_pp, c_opt, x_t, y_t)
            if not global_phase:
                return (cp_pp, c_opt, sp, s_opt, masks, m_opt, ucb), None

            if sharded:
                adv = jax.lax.all_gather(ucb_advantage(ucb), axis,
                                         tiled=True)
                idx = ucb_select_from_advantage(adv, k, select_key(t))
            else:
                idx = ucb_select(ucb, k, select_key(t))
            masks_sel = masks_mod.gather_clients(gather_full(masks), idx)
            mopt_sel = masks_mod.gather_clients(gather_full(m_opt), idx)
            acts_sel = gather_full(acts)[idx]
            ys_sel = gather_full(y_t)[idx]
            if hp.server_grad_to_client:
                cp_sel = masks_mod.gather_clients(gather_full(cp_pp), idx)
                copt_sel = masks_mod.gather_clients(gather_full(c_opt),
                                                    idx)
                (cp_sel, copt_sel, sp, s_opt, masks_sel, mopt_sel, ces,
                 fracs) = global_joint(cp_sel, copt_sel, sp, s_opt,
                                       masks_sel, mopt_sel,
                                       gather_full(x_t)[idx],
                                       ys_sel, acts_sel)
                cp_pp = scatter_back(cp_pp, idx, cp_sel)
                c_opt = scatter_back(c_opt, idx, copt_sel)
            else:
                sp, s_opt, masks_sel, mopt_sel, ces, fracs = global_step(
                    sp, s_opt, masks_sel, mopt_sel, acts_sel, ys_sel)
            masks = scatter_back(masks, idx, masks_sel)
            m_opt = scatter_back(m_opt, idx, mopt_sel)

            sel_mask = jnp.zeros((n,), jnp.float32).at[idx].set(1.0)
            dense = jnp.zeros((n,), jnp.float32).at[idx].set(ces)
            if sharded:
                off = jax.lax.axis_index(axis) * nl
                sel_mask = jax.lax.dynamic_slice_in_dim(sel_mask, off, nl)
                dense = jax.lax.dynamic_slice_in_dim(dense, off, nl)
            ucb = ucb_update(ucb, sel_mask, dense, gamma=gamma)
            carry = (cp_pp, c_opt, sp, s_opt, masks, m_opt, ucb)
            return carry, (idx, ces, fracs)

        return _round_iteration

    def _round_fn(self, T: int, global_phase: bool):
        """One jitted fn running a whole round: scan of the fused
        client-step -> select -> global-step -> UCB-update iteration.
        Cached per (T, global_phase); carries are donated on every
        backend so XLA updates the stacked param/opt/mask pytrees in
        place (and a stale read of a donated buffer fails in CPU tests
        as it would on the chip)."""
        cache_key = (T, global_phase)
        if cache_key in self._round_fns:
            return self._round_fns[cache_key]
        _round_iteration = self._iteration_fn(global_phase)

        # XLA:CPU serializes ops inside a while-loop body onto one
        # thread; fully unrolling short rounds (trip count 1) restores
        # intra-op parallelism at ~2x wall-clock.  Accelerator backends
        # keep the rolled loop (no such penalty, smaller programs).
        on_cpu = jax.default_backend() == "cpu"
        unroll = T if (on_cpu and 1 <= T <= 8) else 1

        def round_fn(carry, xs_round, ys_round, t_idx):
            return jax.lax.scan(_round_iteration, carry,
                                (xs_round, ys_round, t_idx),
                                unroll=unroll)

        round_fn = self._wrap_shard_map(round_fn, staged_cohort_dim=1)
        fn = jax.jit(round_fn, donate_argnums=(0,))
        self._round_fns[cache_key] = fn
        return fn

    def _wrap_shard_map(self, fn, *, staged_cohort_dim: int):
        """Cohort-shard a round/epoch scan driver: carry trees per
        ``self._carry_specs``, staged data with the cohort axis
        (dim ``staged_cohort_dim``) on ``data``, iteration counters and
        the stacked (idx, ces, fracs) outputs replicated (every shard
        computes the identical selection / CE / nnz values, so P() out
        specs just take the one copy).  ``check_vma=False``: the body
        mixes manual collectives with replicated compute, which the
        static varying-axes checker can't see through."""
        if not self._shard:
            return fn
        data_spec = staged_cohort_spec(self._ax, staged_cohort_dim + 1,
                                       cohort_dim=staged_cohort_dim)
        return jax.shard_map(
            fn, mesh=self._mesh,
            in_specs=(self._carry_specs, data_spec, data_spec, P()),
            out_specs=(self._carry_specs, P()),
            check_vma=False)

    def _epoch_fn(self, R: int, T: int, global_phase: bool):
        """One jitted fn running R whole rounds: an outer scan whose
        body applies ``ucb_new_round`` IN-GRAPH at the round boundary
        and then runs the round's inner iteration scan — R x T fused
        iterations per dispatch, zero host round-trips.  Cached per
        (R, T, global_phase); carries donated."""
        cache_key = ("epoch", R, T, global_phase)
        if cache_key in self._round_fns:
            return self._round_fns[cache_key]
        _round_iteration = self._iteration_fn(global_phase)
        gamma = self.hp.gamma

        # Inner scan: same CPU unroll trade-off as _round_fn.  The
        # OUTER scan stays rolled on every backend: XLA compiles the
        # while body once — measured bit-identical to the per-round
        # program AND faster than unrolling R copies (whose fusion
        # across round boundaries both perturbs the last float bit and
        # thrashes the CPU cache with R x T live intermediates).
        on_cpu = jax.default_backend() == "cpu"
        inner_unroll = T if (on_cpu and 1 <= T <= 8) else 1

        def round_body(carry, xs):
            xs_round, ys_round, t_idx = xs
            cp_pp, c_opt, sp, s_opt, masks, m_opt, ucb = carry
            ucb = ucb_new_round(ucb, gamma=gamma)   # round boundary
            # barrier: stops XLA fusing the boundary reset into the
            # first iteration's ucb_update FMA chain, which would
            # perturb l_disc by 1 ULP vs the host-eager new_round the
            # per-round driver performs (bit-interchangeability is the
            # contract the whole reference ladder rests on)
            ucb = jax.lax.optimization_barrier(ucb)
            carry = (cp_pp, c_opt, sp, s_opt, masks, m_opt, ucb)
            return jax.lax.scan(_round_iteration, carry,
                                (xs_round, ys_round, t_idx),
                                unroll=inner_unroll)

        def epoch_fn(carry, xs_ep, ys_ep, t_ep):
            return jax.lax.scan(round_body, carry, (xs_ep, ys_ep, t_ep))

        epoch_fn = self._wrap_shard_map(epoch_fn, staged_cohort_dim=2)
        # The epoch carry only ever flows forward — into the next
        # chunk's dispatch or back into the trainer state — so it is
        # donated and XLA aliases the stacked param/opt pytrees in place.
        fn = jax.jit(epoch_fn, donate_argnums=(0,))
        self._round_fns[cache_key] = fn
        return fn

    @staticmethod
    def _stage_round_np(iters, T: int, n: int):
        """Host-side staging: one round's batches as (T, C, B, ...)."""
        xs_round = np.stack(
            [np.stack([iters[i][t][0] for i in range(n)])
             for t in range(T)])
        ys_round = np.stack(
            [np.stack([iters[i][t][1] for i in range(n)])
             for t in range(T)])
        return xs_round, ys_round

    def _carry(self):
        return ({"c": self.client_params, "p": self.proj_params},
                self.c_opt, self.server_params, self.s_opt, self.masks,
                self.m_opt, self.orch.state)

    def _set_carry(self, carry):
        (cp_pp, self.c_opt, self.server_params, self.s_opt, self.masks,
         self.m_opt, ucb) = carry
        self.client_params, self.proj_params = cp_pp["c"], cp_pp["p"]
        return ucb

    def _run_round_scan(self, iters, T: int, global_phase: bool):
        """Stage the round's data as (T, C, B, ...) once, run the scan,
        then bill meters + orchestrator from ONE device fetch."""
        if T == 0:
            return
        xs_round, ys_round = self._stage_round_np(iters, T, self.n)
        self._dispatch_round(xs_round, ys_round, T, global_phase)

    def _dispatch_round(self, xs_round, ys_round, T: int,
                        global_phase: bool):
        """One round-scan dispatch from pre-staged (T, C, B, ...) host
        arrays (the PR-2 per-round path; epoch_scan replaces the
        per-round sync with one fetch per epoch)."""
        hp = self.hp
        t_idx = jnp.arange(self.orch._n_selects,
                           self.orch._n_selects + T, dtype=jnp.int32)

        fn = self._round_fn(T, global_phase)
        carry, outs = fn(self._carry(),
                         self._put_staged(xs_round, cohort_dim=1),
                         self._put_staged(ys_round, cohort_dim=1), t_idx)
        ucb = self._set_carry(carry)

        acts_shape = (hp.batch_size,) + self._acts_spatial
        if global_phase:
            idx_all, ces_all, fracs_all = jax.device_get(outs)  # one sync
            self.meter.ingest_round(
                acts_shape=acts_shape, batch=hp.batch_size,
                n_clients=self.n, n_iters=T,
                client_flops_per_example=self._fl_c,
                server_flops_per_example=self._fl_s,
                nnz_fracs=fracs_all if hp.act_l1 else None,
                n_selected=idx_all.shape[1],
                grad_down=hp.server_grad_to_client,
                interconnect_bytes=self._iteration_interconnect_bytes()
                * T,
                host_device_bytes=self._staging_bytes_per_round(T))
            self.orch.ingest_round(idx_all, ces_all, state=ucb)
        else:
            self.meter.ingest_round(
                acts_shape=acts_shape, batch=hp.batch_size,
                n_clients=self.n, n_iters=T,
                client_flops_per_example=self._fl_c,
                server_flops_per_example=self._fl_s, n_selected=0,
                host_device_bytes=self._staging_bytes_per_round(T))
            self.orch.state = ucb

    # ------------------------------------------------------------------
    # epoch-resident training: R rounds per dispatch, chunked staging
    # ------------------------------------------------------------------
    def _run_epoch_scan(self, rounds_data, T: int, global_phase: bool):
        """Run a whole epoch of R rounds device-resident.

        rounds_data: per-round ``(xs, ys)`` numpy arrays, each
        (T, C, B, ...) — or zero-arg callables producing them, invoked
        LAZILY in round order as each chunk is staged, so host memory
        holds at most two chunks of batches regardless of R (the
        ``epoch_chunk_rounds`` knob bounds host staging and device
        residency alike).  Rounds are dispatched in chunks of
        ``epoch_chunk_rounds`` (0 = all R in ONE dispatch) through a
        two-slot staging ring: chunk k+1's ``device_put`` is issued
        right after chunk k's (async) scan dispatch, so the host->device
        copy overlaps chunk k's compute.  The carry flows across chunks
        as device references — selections / CE losses / nnz fractions
        from every chunk come back in exactly ONE ``device_get`` at the
        epoch's end, absorbed by ``Meter.ingest_epoch`` and
        ``Orchestrator.ingest_epoch``.  Returns the per-round cumulative
        meter summaries.
        """
        hp = self.hp
        R = len(rounds_data)
        if R == 0 or T == 0:
            return []
        chunk = hp.epoch_chunk_rounds or R
        chunk = max(1, min(chunk, R))
        base = self.orch._n_selects

        def stage(r0, rc):
            with span("train.feed"):
                rds = [rounds_data[r]() if callable(rounds_data[r])
                       else rounds_data[r] for r in range(r0, r0 + rc)]
                with span("train.stack", bytes=sum(
                        rd[0].nbytes + rd[1].nbytes for rd in rds)):
                    xs = np.stack([rd[0] for rd in rds])
                    ys = np.stack([rd[1] for rd in rds])
                t_idx = (base + (r0 + np.arange(rc))[:, None] * T
                         + np.arange(T)[None, :]).astype(np.int32)
                with span("train.put",
                          bytes=xs.nbytes + ys.nbytes + t_idx.nbytes):
                    return (self._put_staged(xs, cohort_dim=2),
                            self._put_staged(ys, cohort_dim=2),
                            jax.device_put(t_idx))

        starts = list(range(0, R, chunk))
        ring = [stage(0, min(chunk, R))]            # slot 0: first chunk
        carry, outs_all = self._carry(), []
        for ci, r0 in enumerate(starts):
            rc = min(chunk, R - r0)
            fn = self._epoch_fn(rc, T, global_phase)
            with span("train.dispatch"):
                carry, outs = fn(carry, *ring.pop(0))   # async dispatch
            if ci + 1 < len(starts):                # slot 1: next chunk's
                n0 = starts[ci + 1]                 # H2D overlaps compute
                ring.append(stage(n0, min(chunk, R - n0)))
            outs_all.append(outs)
        ucb = self._set_carry(carry)

        acts_shape = (hp.batch_size,) + self._acts_spatial
        bill = dict(acts_shape=acts_shape, batch=hp.batch_size,
                    n_clients=self.n, n_iters=T,
                    client_flops_per_example=self._fl_c,
                    server_flops_per_example=self._fl_s,
                    host_device_bytes=self._staging_bytes_per_round(T))
        if global_phase:
            with span("train.sync"):
                fetched = jax.device_get(outs_all)  # the ONE epoch sync
            with span("train.bill"):
                idx_all = np.concatenate([f[0] for f in fetched])
                ces_all = np.concatenate([f[1] for f in fetched])
                fracs_all = np.concatenate([f[2] for f in fetched])
                summaries = self.meter.ingest_epoch(
                    n_rounds=R, nnz_fracs=fracs_all if hp.act_l1 else None,
                    n_selected=idx_all.shape[-1],
                    grad_down=hp.server_grad_to_client,
                    interconnect_bytes=self._iteration_interconnect_bytes()
                    * T, **bill)
                self.orch.ingest_epoch(idx_all, ces_all, state=ucb)
        else:
            with span("train.bill"):
                summaries = self.meter.ingest_epoch(n_rounds=R,
                                                    n_selected=0, **bill)
                self.orch.ingest_epoch(None, None, state=ucb, n_rounds=R)
        return summaries

    # ------------------------------------------------------------------
    # streamed rounds: client store residency, two commuting passes
    # ------------------------------------------------------------------
    def _client_pass_fn(self, T: int, m: int):
        """One jitted fn scanning the vmapped client step over a round's
        T iterations for an (m, ...)-row streamed chunk, returning the
        updated rows + the stacked (T, m, B, ...) split activations.
        A chunk placed with its cohort axis on ``data`` runs under
        ``shard_map`` (Pallas kernels are not partitioned
        automatically).  Cached per (T, m); chunk rows are donated."""
        cache_key = ("stream", T, m)
        if cache_key in self._round_fns:
            return self._round_fns[cache_key]
        client_step = self._client_step_fn
        on_cpu = jax.default_backend() == "cpu"
        unroll = T if (on_cpu and 1 <= T <= 8) else 1

        def chunk_fn(cp, co, xs, ys):
            def body(carry, xy):
                cp, co = carry
                x, y = xy
                cp, co, _, acts = client_step(cp, co, x, y)
                return (cp, co), acts

            (cp, co), acts = jax.lax.scan(body, (cp, co), (xs, ys),
                                          unroll=unroll)
            return cp, co, acts

        if self._shard and m % self._ax.data_size == 0:
            rows = P(self._ax.data_spec)                 # (m, ...) leaves
            staged = P(None, self._ax.data_spec)         # (T, m, ...)
            chunk_fn = jax.shard_map(
                chunk_fn, mesh=self._mesh,
                in_specs=(rows, rows, staged, staged),
                out_specs=(rows, rows, staged), check_vma=False)
        fn = jax.jit(chunk_fn, donate_argnums=(0, 1))
        self._round_fns[cache_key] = fn
        return fn

    def _stream_one_round(self, ucb, t_base: int, iters, T: int,
                          global_phase: bool):
        """One streamed round over the client store: the two passes that
        commute exactly with the resident interleaving (client steps
        never read what global steps write — the ``server_grad_to_client``
        ablation, which breaks this, falls back to resident at init).

        Pass A streams every client's params/opt rows through the device
        in ``stream_chunk`` cohorts (two-slot ring: chunk k+1's store
        gather + H2D overlaps chunk k's scan), spilling split
        activations to a host buffer.  Pass B re-runs the round's global
        iterations against the spilled activations: selection resolves
        FIRST on the device-resident UCB state, then only the selected
        S rows stage in and out.  Returns the final UCB state + the
        round's (T, k) selections / CE losses / nnz fractions (host),
        without touching the meter or orchestrator — callers bill at
        their own cadence."""
        hp = self.hp
        n = self.n
        chunk = self._stream_chunk
        use_scan = hp.round_scan and hp.global_batch
        acts_all = None
        ys_all = None

        def stage(i0, m):
            rows = np.arange(i0, i0 + m)
            xs = np.stack([np.stack([iters[i][t][0]
                                     for i in range(i0, i0 + m)])
                           for t in range(T)])
            ys = np.stack([np.stack([iters[i][t][1]
                                     for i in range(i0, i0 + m)])
                           for t in range(T)])
            g = self.store.gather(rows, ("cp", "co"))
            return (rows, ys,
                    self._stream_put_data(xs, m),
                    self._stream_put_data(ys, m),
                    self._stream_put_rows(g["cp"], m),
                    self._stream_put_rows(g["co"], m))

        # ---- pass A: chunked client pass over ALL rows ---------------
        starts = list(range(0, n, chunk))
        ring = [stage(0, min(chunk, n))]
        for ci, i0 in enumerate(starts):
            m = min(chunk, n - i0)
            rows, ys_np, xs_d, ys_d, cp_d, co_d = ring.pop(0)
            if use_scan:
                cp_d, co_d, acts = self._client_pass_fn(T, m)(
                    cp_d, co_d, xs_d, ys_d)
            else:
                # eager rung: one dispatch per protocol iteration
                acc = []
                for t in range(T):
                    cp_d, co_d, _, a = self._client_step(
                        cp_d, co_d, xs_d[t], ys_d[t])
                    acc.append(a)
                acts = jnp.stack(acc)
            if ci + 1 < len(starts):        # two-slot ring: next chunk's
                n0 = starts[ci + 1]         # gather + H2D overlaps this
                ring.append(stage(n0, min(chunk, n - n0)))
            acts_np = np.asarray(acts)      # drain: activation spill D2H
            if acts_all is None:
                acts_all = np.empty((T, n) + acts_np.shape[2:],
                                    acts_np.dtype)
                ys_all = np.empty((T, n) + ys_np.shape[2:], ys_np.dtype)
            acts_all[:, i0:i0 + m] = acts_np
            ys_all[:, i0:i0 + m] = ys_np
            self.store.scatter(rows, {"cp": cp_d, "co": co_d})

        if not global_phase:
            return ucb, None, None, None

        # ---- pass B: per-iteration select -> gather -> global step ---
        k = self.orch.k
        idx_all = np.empty((T, k), np.int32)
        ces_l, fracs_l = [], []
        for t in range(T):
            idx = self.orch.select_on(ucb, t_base + t)
            idx_np = np.asarray(idx)        # selection resolves before
            sel = self.store.gather(idx_np, ("m", "mo"))  # staging
            (self.server_params, self.s_opt, masks_sel, mopt_sel, ces,
             fracs) = self._global_step(
                self.server_params, self.s_opt, sel["m"], sel["mo"],
                jnp.asarray(acts_all[t, idx_np]),
                jnp.asarray(ys_all[t, idx_np]))
            ucb = self.orch.update_on(ucb, idx, ces)
            self.store.scatter(idx_np, {"m": masks_sel, "mo": mopt_sel})
            idx_all[t] = idx_np
            ces_l.append(ces)
            fracs_l.append(fracs)
        ces_all, fracs_all = jax.device_get((ces_l, fracs_l))
        return ucb, idx_all, np.stack(ces_all), np.stack(fracs_all)

    def _run_round_streamed(self, iters, T: int, global_phase: bool):
        """Streamed counterpart of ``_run_round_scan`` /
        ``_dispatch_round``: same billing shape (one ``ingest_round`` +
        ``ingest_round`` orchestrator replay per round), with the store
        gather/scatter + activation-spill traffic added on the
        ``host_device_bytes`` channel — the protocol channels are
        billed with IDENTICAL arguments, so bandwidth / FLOP totals are
        residency-invariant."""
        if T == 0:
            return
        hp = self.hp
        ucb, idx_all, ces_all, fracs_all = self._stream_one_round(
            self.orch.state, self.orch._n_selects, iters, T, global_phase)
        acts_shape = (hp.batch_size,) + self._acts_spatial
        hd = (self._staging_bytes_per_round(T)
              + self._stream_store_bytes(T, global_phase))
        if global_phase:
            self.meter.ingest_round(
                acts_shape=acts_shape, batch=hp.batch_size,
                n_clients=self.n, n_iters=T,
                client_flops_per_example=self._fl_c,
                server_flops_per_example=self._fl_s,
                nnz_fracs=fracs_all if hp.act_l1 else None,
                n_selected=idx_all.shape[1],
                grad_down=hp.server_grad_to_client,
                host_device_bytes=hd)
            self.orch.ingest_round(idx_all, ces_all, state=ucb)
        else:
            self.meter.ingest_round(
                acts_shape=acts_shape, batch=hp.batch_size,
                n_clients=self.n, n_iters=T,
                client_flops_per_example=self._fl_c,
                server_flops_per_example=self._fl_s, n_selected=0,
                host_device_bytes=hd)
            self.orch.state = ucb

    def _run_epoch_streamed(self, R: int, T: int, global_phase: bool,
                            make_iters):
        """Streamed counterpart of ``_run_epoch_scan``: R rounds with
        the round boundary's ``ucb_new_round`` applied to the live
        device state between streamed rounds, billed by ONE
        ``ingest_epoch`` / ``Orchestrator.ingest_epoch`` pair — history
        records bit-match the resident epoch driver's."""
        hp = self.hp
        ucb = self.orch.state
        base = self.orch._n_selects
        idx_r, ces_r, fracs_r = [], [], []
        for r in range(R):
            ucb = ucb_new_round(ucb, gamma=hp.gamma)  # round boundary
            ucb, idx, ces, fracs = self._stream_one_round(
                ucb, base + r * T, make_iters(), T, global_phase)
            if global_phase:
                idx_r.append(idx)
                ces_r.append(ces)
                fracs_r.append(fracs)
        acts_shape = (hp.batch_size,) + self._acts_spatial
        bill = dict(acts_shape=acts_shape, batch=hp.batch_size,
                    n_clients=self.n, n_iters=T,
                    client_flops_per_example=self._fl_c,
                    server_flops_per_example=self._fl_s,
                    host_device_bytes=self._staging_bytes_per_round(T)
                    + self._stream_store_bytes(T, global_phase))
        if global_phase:
            summaries = self.meter.ingest_epoch(
                n_rounds=R,
                nnz_fracs=np.stack(fracs_r) if hp.act_l1 else None,
                n_selected=idx_r[0].shape[1],
                grad_down=hp.server_grad_to_client, **bill)
            self.orch.ingest_epoch(np.stack(idx_r), np.stack(ces_r),
                                   state=ucb)
        else:
            summaries = self.meter.ingest_epoch(n_rounds=R, n_selected=0,
                                                **bill)
            self.orch.ingest_epoch(None, None, state=ucb, n_rounds=R)
        return summaries

    def client_state(self):
        """Host copies of the stacked per-client state as the store's
        dict-of-groups view — the residency-agnostic accessor used by
        checkpoints and the streamed-vs-resident differential tests."""
        if self._streamed:
            return self.store.full()
        return jax.tree.map(np.asarray, {
            "cp": {"c": self.client_params, "p": self.proj_params},
            "co": self.c_opt, "m": self.masks, "mo": self.m_opt})

    # ------------------------------------------------------------------
    def _client_slice(self, tree, i):
        return jax.tree.map(lambda l: l[i], tree)

    def _set_client_slice(self, tree, i, new):
        return jax.tree.map(lambda l, n: l.at[i].set(n), tree, new)

    def _payload_bytes(self, acts_shape, batch,
                       nnz_fraction: Optional[float] = None):
        """Bytes crossing the split for ONE selected client this iteration.

        nnz_fraction is that client's own activation sparsity (None when
        activation sparsification is off) — billing is strictly
        per-client, never a stale value from another client.
        """
        return split_payload_bytes(
            acts_shape, batch, nnz_fraction=nnz_fraction,
            grad_down=self.hp.server_grad_to_client)

    # ------------------------------------------------------------------
    def _global_iteration(self, selected, acts, xs, ys):
        """One batched global-phase iteration over the selected clients.

        Exactly one host-device sync: per-client CE losses and payload
        nnz fractions come back together via a single ``device_get``.
        """
        hp = self.hp
        idx = jnp.asarray(np.asarray(selected))
        masks_sel = masks_mod.gather_clients(self.masks, idx)
        mopt_sel = masks_mod.gather_clients(self.m_opt, idx)
        acts_sel = acts[idx]
        ys_sel = jnp.asarray(ys[np.asarray(selected)])

        if hp.server_grad_to_client:
            cp_sel = masks_mod.gather_clients(
                {"c": self.client_params, "p": self.proj_params}, idx)
            copt_sel = masks_mod.gather_clients(self.c_opt, idx)
            (cp_sel, copt_sel, self.server_params, self.s_opt, masks_sel,
             mopt_sel, ces, fracs) = self._global_joint_step(
                cp_sel, copt_sel, self.server_params, self.s_opt,
                masks_sel, mopt_sel, jnp.asarray(xs[np.asarray(selected)]),
                ys_sel, acts_sel)
            self.client_params = masks_mod.scatter_clients(
                self.client_params, idx, cp_sel["c"])
            self.proj_params = masks_mod.scatter_clients(
                self.proj_params, idx, cp_sel["p"])
            self.c_opt = masks_mod.scatter_clients(self.c_opt, idx, copt_sel)
        else:
            (self.server_params, self.s_opt, masks_sel, mopt_sel, ces,
             fracs) = self._global_step(
                self.server_params, self.s_opt, masks_sel, mopt_sel,
                acts_sel, ys_sel)

        self.masks = masks_mod.scatter_clients(self.masks, idx, masks_sel)
        self.m_opt = masks_mod.scatter_clients(self.m_opt, idx, mopt_sel)

        losses, fracs = jax.device_get((ces, fracs))  # the one sync
        acts_shape = acts.shape[1:]
        fl_s = self._fl_s
        for k in range(len(selected)):
            nnz = float(fracs[k]) if hp.act_l1 else None
            self.meter.add_payload(
                self._payload_bytes(acts_shape, hp.batch_size, nnz))
            self.meter.add_server_flops(3 * fl_s * hp.batch_size)
        return [float(l) for l in losses]

    def _global_iteration_loop(self, selected, acts, xs, ys):
        """Seed reference: per-client host loop (one dispatch + one
        host sync per selected client).  Kept for differential tests and
        the ``benchmarks/global_phase`` comparison."""
        hp = self.hp
        losses = []
        for i in selected:
            a_i = acts[i]
            nnz = None
            if hp.act_l1:
                nnz = float(jnp.mean((jnp.abs(a_i) > hp.act_threshold)))
                a_i = jnp.where(jnp.abs(a_i) > hp.act_threshold, a_i, 0)
            mask_i = self._client_slice(self.masks, i)
            mopt_i = self._client_slice(self.m_opt, i)
            if hp.server_grad_to_client:
                cp_i = self._client_slice(
                    {"c": self.client_params, "p": self.proj_params}, i)
                copt_i = self._client_slice(self.c_opt, i)
                (cp_i, copt_i, self.server_params, self.s_opt,
                 mask_i, mopt_i, ce) = self._joint_step(
                    cp_i, copt_i, self.server_params, self.s_opt,
                    mask_i, mopt_i, jnp.asarray(xs[i]), jnp.asarray(ys[i]))
                self.client_params = self._set_client_slice(
                    self.client_params, i, cp_i["c"])
                self.proj_params = self._set_client_slice(
                    self.proj_params, i, cp_i["p"])
                self.c_opt = self._set_client_slice(self.c_opt, i, copt_i)
            else:
                (self.server_params, self.s_opt, mask_i, mopt_i,
                 ce) = self._server_step(
                    self.server_params, self.s_opt, mask_i, mopt_i,
                    a_i, jnp.asarray(ys[i]))
            self.masks = self._set_client_slice(self.masks, i, mask_i)
            self.m_opt = self._set_client_slice(self.m_opt, i, mopt_i)
            losses.append(float(ce))
            self.meter.add_payload(
                self._payload_bytes(a_i.shape, hp.batch_size, nnz))
            self.meter.add_server_flops(3 * self._fl_s * hp.batch_size)
        return losses

    # ------------------------------------------------------------------
    def train(self, log_every: int = 1, eval_every: int = 1):
        hp, cfg = self.hp, self.cfg
        local_rounds = int(round(hp.kappa * hp.rounds))
        fl_c = self._fl_c
        use_scan = hp.round_scan and hp.global_batch
        if hp.epoch_scan and use_scan:
            with span("train"):
                return self._train_epoch_scan(eval_every)
        global_iter = (self._global_iteration if hp.global_batch
                       else self._global_iteration_loop)

        for r in range(hp.rounds):
            global_phase = r >= local_rounds
            self.orch.new_round()
            iters = [list(self._epoch_batches(i)) for i in range(self.n)]
            T = min(len(it) for it in iters)
            if self._streamed:
                # same batches, same selection keys — only residency
                # differs (pass A picks the use_scan dispatch style)
                self._run_round_streamed(iters, T, global_phase)
            elif use_scan:
                self._run_round_scan(iters, T, global_phase)
            else:
                for t in range(T):
                    xs = np.stack([iters[i][t][0] for i in range(self.n)])
                    ys = np.stack([iters[i][t][1] for i in range(self.n)])
                    cp_pp = {"c": self.client_params, "p": self.proj_params}
                    new, self.c_opt, closs, acts = self._client_step(
                        cp_pp, self.c_opt, jnp.asarray(xs), jnp.asarray(ys))
                    self.client_params, self.proj_params = new["c"], new["p"]
                    # 3x forward FLOPs for fwd+bwd
                    self.meter.add_client_flops(
                        3 * fl_c * self.n * hp.batch_size)
                    # per-iteration (C, B, ...) upload — sums to the
                    # same round total the scan drivers bill
                    self.meter.add_host_device(
                        self._staging_bytes_per_round(1))

                    if not global_phase:
                        continue
                    selected = self.orch.select()
                    losses = global_iter(selected, acts, xs, ys)
                    self.orch.update(selected, losses)

            rec = {"round": r, "phase": "global" if global_phase else "local",
                   **self.meter.summary()}
            if (r + 1) % eval_every == 0 or r == hp.rounds - 1:
                rec["accuracy"] = self.evaluate()
            self.history.append(rec)
        return self.history

    def _train_epoch_scan(self, eval_every: int):
        """Epoch-resident driver: consecutive rounds sharing a phase are
        grouped into one epoch (cut at eval points, where the host needs
        the params anyway) and run through ``_run_epoch_scan`` — R x T
        iterations per dispatch group, ONE ``device_get`` each.  History
        records per round are reconstructed from the epoch's stacked
        outputs, bit-identical to the per-round-dispatch driver's."""
        hp = self.hp
        local_rounds = int(round(hp.kappa * hp.rounds))
        # T is a pure function of the data sizes (batch_iterator drops
        # the remainder), so it is known before any batches are drawn
        T = min(len(c.x) // hp.batch_size for c in self.clients)

        def is_eval(r):
            return (r + 1) % eval_every == 0 or r == hp.rounds - 1

        def make_iters():
            """One round's per-client batch lists, drawn from the SAME
            per-client RNG stream (and in the same order) as the eager
            drivers."""
            iters = [list(self._epoch_batches(i)) for i in range(self.n)]
            assert min(len(it) for it in iters) == T
            return iters

        def make_round():
            """One round's staged data.  Called lazily by the staging
            ring — at most two chunks of batches are ever materialized
            on the host."""
            with span("train.draw"):
                iters = make_iters()
            with span("train.stack",
                      bytes=int(self._staging_bytes_per_round(T))):
                return self._stage_round_np(iters, T, self.n)

        r = 0
        while r < hp.rounds:
            global_phase = r >= local_rounds
            end = r
            while (end + 1 < hp.rounds and not is_eval(end)
                   and ((end + 1) >= local_rounds) == global_phase):
                end += 1
            R = end - r + 1                 # rounds r..end = one epoch
            if T == 0:
                # nothing to run, but the per-round driver still resets
                # the bandit each round — keep the ladder interchangeable
                summaries = []
                for _ in range(R):
                    self.orch.new_round()
            elif self._streamed:
                summaries = self._run_epoch_streamed(R, T, global_phase,
                                                     make_iters)
            else:
                with span("train.epoch"):
                    summaries = self._run_epoch_scan([make_round] * R, T,
                                                     global_phase)
            for j, rr in enumerate(range(r, end + 1)):
                rec = {"round": rr,
                       "phase": "global" if global_phase else "local",
                       **(summaries[j] if j < len(summaries)
                          else self.meter.summary())}
                if is_eval(rr):        # only possible at the epoch end
                    with span("train.eval"):
                        rec["accuracy"] = self.evaluate()
                self.history.append(rec)
            r = end + 1
        return self.history

    # ------------------------------------------------------------------
    def _epoch_batches(self, i):
        from repro.data.synthetic import batch_iterator
        return batch_iterator(self.clients[i], self.hp.batch_size, self._rng)

    # ------------------------------------------------------------------
    def evaluate(self) -> float:
        if self._streamed:
            return self._evaluate_streamed()
        shapes = {cd.test_x.shape for cd in self.clients}
        if len(shapes) == 1:
            xs = jnp.asarray(np.stack([cd.test_x for cd in self.clients]))
            ys = jnp.asarray(np.stack([cd.test_y for cd in self.clients]))
            accs = self._eval_all(self.client_params, self.server_params,
                                  self.masks, xs, ys)
            return 100.0 * float(jnp.mean(accs))
        accs = []
        for i, cd in enumerate(self.clients):
            cp = self._client_slice(self.client_params, i)
            mask_i = self._client_slice(self.masks, i)
            acc = self._eval_client(cp, self.server_params, mask_i,
                                    jnp.asarray(cd.test_x),
                                    jnp.asarray(cd.test_y))
            accs.append(float(acc))
        return 100.0 * float(np.mean(accs))

    def _evaluate_streamed(self) -> float:
        """Chunked evaluation over the client store — only O(chunk)
        client rows are ever device-resident."""
        shapes = {cd.test_x.shape for cd in self.clients}
        chunk = self._stream_chunk
        if len(shapes) == 1:
            accs = np.empty((self.n,), np.float32)
            for i0 in range(0, self.n, chunk):
                m = min(chunk, self.n - i0)
                rows = np.arange(i0, i0 + m)
                g = self.store.gather(rows, ("cp", "m"))
                xs = jnp.asarray(np.stack(
                    [self.clients[i].test_x for i in rows]))
                ys = jnp.asarray(np.stack(
                    [self.clients[i].test_y for i in rows]))
                accs[i0:i0 + m] = np.asarray(self._eval_all(
                    g["cp"]["c"], self.server_params, g["m"], xs, ys))
            return 100.0 * float(np.mean(accs))
        accs = []
        for i, cd in enumerate(self.clients):
            g = self.store.gather(np.asarray([i]), ("cp", "m"))
            cp = jax.tree.map(lambda l: l[0], g["cp"]["c"])
            mask_i = jax.tree.map(lambda l: l[0], g["m"])
            acc = self._eval_client(cp, self.server_params, mask_i,
                                    jnp.asarray(cd.test_x),
                                    jnp.asarray(cd.test_y))
            accs.append(float(acc))
        return 100.0 * float(np.mean(accs))

    def c3(self, bandwidth_budget, compute_budget, temperature=8.0):
        acc = self.history[-1].get("accuracy") or self.evaluate()
        return c3_score(acc, self.meter.bandwidth_gb,
                        self.meter.client_tflops,
                        bandwidth_budget=bandwidth_budget,
                        compute_budget=compute_budget,
                        temperature=temperature)
