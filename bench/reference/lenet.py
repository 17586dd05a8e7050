"""Plain reference of AdaSplit split training on the LeNet backbone.

It follows the protocol as the paper states it (§3), one global iteration
at a time: every client takes one Adam step on its supervised NT-Xent
loss (eq. 5) through its own tower and projection head; in the global
phase the UCB orchestrator (eq. 6) picks ``k = round(eta * C)`` clients,
the server takes one Adam step on the mean of their cross-entropies plus
``lam`` times the mask L1 term, each selected client's unit mask takes
its own Adam step (eq. 7-8), and the bandit appends the iteration.  A
round opens by resetting the bandit's history to its last losses.

The reference can be handed the selections a run under test made, and
then follows them (as a served model's reference follows the served
tokens), while it still scores every client itself: what it reports of
selection is how far the chosen clients fall below its own top k.

Parameter trees use the program's layout (``client``: one dict per conv
block; ``proj``: a two-layer head; ``server``: conv blocks, ``fc1``,
``fc2``, ``head``; unit masks per server block and FC layer), so the
benchmark can hand the same weights to both.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import lenet_acts_dim, lenet_blocks, lenet_split

JITTER = 2e-7           # the orchestrator's tie-break scale, as documented


# ---------------------------------------------------------------------------
# weights: made by the benchmark from the seed
# ---------------------------------------------------------------------------


def init_weights(cfg: dict, n_clients: int, key):
    """Every trainable of an AdaSplit LeNet population, float32, in one
    call (jit it): per-client towers and heads stacked on a leading C
    axis, one server, unit masks at one."""
    k = cfg["kernel"]
    s = lenet_split(cfg)
    blocks = lenet_blocks(cfg)
    keys = iter(jax.random.split(key, 64))

    def conv(shape_lead, cin, cout):
        w = jax.random.normal(next(keys), shape_lead + (k, k, cin, cout))
        return {"w": w * np.sqrt(2.0 / (k * k * cin)),
                "b": 0.05 * jax.random.normal(next(keys), shape_lead + (cout,))}

    def dense(lead, din, dout, scale, bias=True):
        out = {"w": jax.random.normal(next(keys), lead + (din, dout)) * scale}
        if bias:
            out["b"] = 0.05 * jax.random.normal(next(keys), lead + (dout,))
        return out

    C = (n_clients,)
    client = {"blocks": [conv(C, ci, co) for _, ci, co in blocks[:s]]}
    d, h, p = lenet_acts_dim(cfg), cfg["proj_hidden"], cfg["proj_dim"]
    w1 = dense(C, d, h, 1 / np.sqrt(d))
    proj = {"w1": w1["w"], "b1": w1["b"],
            "w2": dense(C, h, p, 1 / np.sqrt(h), bias=False)["w"]}
    flat = max(blocks[-1][0] // 2, 1) ** 2 * cfg["conv_channels"][-1]
    server = {"blocks": [conv((), ci, co) for _, ci, co in blocks[s:]],
              "fc1": dense((), flat, cfg["fc_hidden"], np.sqrt(2.0 / flat)),
              "fc2": dense((), cfg["fc_hidden"], cfg["d_model"],
                           np.sqrt(2.0 / cfg["fc_hidden"])),
              "head": dense((), cfg["d_model"], cfg["n_classes"], 0.05)}
    masks = {"blocks": [jnp.ones(C + (co,)) for _, _, co in blocks[s:]],
             "fc1": jnp.ones(C + (cfg["fc_hidden"],)),
             "fc2": jnp.ones(C + (cfg["d_model"],))}
    return {"client": client, "proj": proj, "server": server, "masks": masks}


def leaves(tree) -> Dict[str, np.ndarray]:
    """Host copies of a tree's leaves keyed by path."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(v, np.float32)
            for p, v in flat}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _conv_block(p, x, gate=None):
    """Same-padded KxK conv as one matmul over the KxK patches (taps in
    (row, column, channel) order, the filter's own), bias, ReLU, the
    optional per-example unit gate, then 2x2 max-pool."""
    kh, kw, cin, cout = p["w"].shape
    B, H, W, _ = x.shape
    xp = jnp.pad(x, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2), (0, 0)))
    patches = jnp.concatenate([xp[:, i:i + H, j:j + W, :]
                               for i in range(kh) for j in range(kw)], -1)
    y = patches @ p["w"].astype(x.dtype).reshape(kh * kw * cin, cout)
    y = jax.nn.relu(y + p["b"].astype(x.dtype))
    if gate is not None:                     # (B, Cout) per example
        y = y * gate.astype(x.dtype)[:, None, None, :]
    return y.reshape(B, H // 2, 2, W // 2, 2, cout).max(axis=(2, 4))


def client_forward(cp, x):
    for bp in cp["blocks"]:
        x = _conv_block(bp, x)
    return x


def project(pp, acts):
    h = acts.reshape(acts.shape[0], -1)
    z = jax.nn.relu(h @ pp["w1"].astype(h.dtype) + pp["b1"].astype(h.dtype))
    return z @ pp["w2"].astype(h.dtype)


def ntxent(q, y, tau):
    q = q.astype(jnp.float32)
    q = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-8)
    n = q.shape[0]
    sim = q @ q.T / tau
    eye = jnp.eye(n, dtype=bool)
    sim = jnp.where(eye, -jnp.inf, sim)
    logp = sim - jax.nn.logsumexp(sim, axis=-1, keepdims=True)
    pos = (y[:, None] == y[None, :]) & ~eye
    return -jnp.sum(jnp.where(pos, logp, 0.0)) / jnp.maximum(jnp.sum(pos), 1)


def server_forward(sp, gates, acts):
    """gates: per-example unit masks, leaves (B, U)."""
    x = acts
    for bp, g in zip(sp["blocks"], gates["blocks"]):
        x = _conv_block(bp, x, g)
    x = x.reshape(x.shape[0], -1)
    for name in ("fc1", "fc2"):
        p = sp[name]
        x = jax.nn.relu(x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype))
        x = x * gates[name].astype(x.dtype)
    p = sp["head"]
    return (x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)).astype(
        jnp.float32)


def cross_entropy(logits, y):
    return jnp.mean(jax.nn.logsumexp(logits, -1)
                    - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])


def adam(p, g, mu, nu, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One Adam step on one array; ``t`` is the step number after it."""
    g = g.astype(jnp.float32)
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    tf = t.astype(jnp.float32)
    mhat = mu / (1 - b1 ** tf)
    nhat = nu / (1 - b2 ** tf)
    new = p.astype(jnp.float32) - lr * mhat / (jnp.sqrt(nhat) + eps)
    return new.astype(p.dtype), mu, nu


def adam_tree(params, grads, mu, nu, t, lr):
    out = jax.tree.map(lambda p, g, m, v: adam(p, g, m, v, t, lr),
                       params, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


# ---------------------------------------------------------------------------
# the bandit (eq. 6)
# ---------------------------------------------------------------------------


def ucb_init(n, gamma, init_loss=100.0):
    return {"l": jnp.full((n,), init_loss * (1 + gamma), jnp.float32),
            "s": jnp.full((n,), 1 + gamma, jnp.float32),
            "last": jnp.full((n,), init_loss, jnp.float32),
            "prev": jnp.full((n,), init_loss, jnp.float32),
            "t": jnp.asarray(2, jnp.int32)}


def ucb_new_round(u, gamma):
    n = u["last"].shape[0]
    return {"l": u["last"] * (1 + gamma),
            "s": jnp.full((n,), 1 + gamma, jnp.float32),
            "last": u["last"], "prev": u["last"],
            "t": jnp.asarray(2, jnp.int32)}


def ucb_scores(u, key):
    """Advantage plus the keyed tie-break jitter: the orchestrator's
    ranking of every client."""
    s = jnp.maximum(u["s"], 1e-8)
    t = jnp.maximum(u["t"], 2).astype(jnp.float32)
    a = u["l"] / s + jnp.sqrt(2.0 * jnp.log(t) / s)
    jit = jax.random.uniform(key, a.shape, jnp.float32, 0.0, 1.0)
    return a + jit * JITTER * (1.0 + jnp.max(jnp.abs(a)))


def ucb_update(u, sel, losses, gamma):
    new_l = jnp.where(sel > 0, losses, (u["last"] + u["prev"]) / 2.0)
    return {"l": gamma * u["l"] + new_l, "s": gamma * u["s"] + sel,
            "last": new_l, "prev": u["last"], "t": u["t"] + 1}


@jax.jit
def _ucb_step(u, key, idx, ces, gamma):
    scores = ucb_scores(u, key)
    n = u["last"].shape[0]
    sel = jnp.zeros((n,), jnp.float32).at[idx].set(1.0)
    dense = jnp.zeros((n,), jnp.float32).at[idx].set(ces)
    return scores, ucb_update(u, sel, dense, gamma)


def ucb_replay(n: int, hp: dict, key, steps):
    """The bandit's scores before every global iteration of ``steps`` (per
    call, per global iteration: the selected ids and their losses), fed
    the selections and losses the run itself reported: what the
    orchestrator should have ranked, whatever the losses were."""
    R = hp["rounds"]
    local = int(round(hp["kappa"] * R))
    gamma = jnp.float32(hp["gamma"])
    u, counter, out = ucb_init(n, hp["gamma"]), 0, []
    for its in steps:
        T = len(its) // max(R - local, 1)
        scores, j = [], 0
        for r in range(R):
            u = ucb_new_round(u, hp["gamma"])
            if r < local:
                continue
            for _ in range(T):
                idx, ces = its[j]
                j += 1
                sc, u = _ucb_step(u, jax.random.fold_in(key, counter),
                                  jnp.asarray(idx, jnp.int32),
                                  jnp.asarray(ces, jnp.float32), gamma)
                counter += 1
                scores.append(np.asarray(sc))
        out.append(scores)
    return out


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------


def _zeros(tree):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)


class Protocol:
    """AdaSplit over a population, round by round.

    dtype: the compute and parameter type ("float32" is the reference;
    "bfloat16" is the control, the same protocol one precision lower).
    precision: matmul precision, "highest" for float32 by default;
    "default" (one bfloat16 pass on a TPU) is what a float32 program
    gets unless it asks for more, a witness for the program's own
    rounding.
    fault: None, or "half" (every loss over the first half of its batch
    only), for the fault checks.
    """

    def __init__(self, cfg: dict, hp: dict, weights, seed_key, *,
                 dtype: str = "float32", fault: Optional[str] = None,
                 precision: Optional[str] = None):
        self.cfg, self.hp = cfg, hp
        self.dtype = jnp.dtype(dtype)
        self.precision = precision or (
            "highest" if self.dtype == jnp.float32 else "default")
        self.fault = fault
        cast = lambda t: jax.tree.map(lambda x: x.astype(self.dtype), t)
        self.cp = {"c": cast(weights["client"]), "p": cast(weights["proj"])}
        self.sp = cast(weights["server"])
        self.masks = cast(weights["masks"])
        n = jax.tree.leaves(self.masks)[0].shape[0]
        self.n = n
        self.k = max(1, int(round(hp["eta"] * n)))
        self.c_mu, self.c_nu = _zeros(self.cp), _zeros(self.cp)
        self.c_t = jnp.zeros((n,), jnp.int32)
        self.s_mu, self.s_nu = _zeros(self.sp), _zeros(self.sp)
        self.s_t = jnp.asarray(0, jnp.int32)
        self.m_mu, self.m_nu = _zeros(self.masks), _zeros(self.masks)
        self.m_t = jnp.zeros((n,), jnp.int32)
        self.ucb = ucb_init(n, hp["gamma"])
        self.key = seed_key
        self.n_selects = 0
        self.first_grads: Dict[str, float] = {}
        self._round = {g: jax.jit(self._make_round(g)) for g in (False, True)}

    # -- one global iteration ----------------------------------------
    def _half(self, *xs):
        if self.fault != "half":
            return xs
        return tuple(x[:, : x.shape[1] // 2] if x.ndim > 1 else x
                     for x in xs)

    def _client_step(self, cp, mu, nu, t, x, y):
        hp = self.hp

        def loss(cpi, xi, yi):
            acts = client_forward(cpi["c"], xi)
            return ntxent(project(cpi["p"], acts), yi, hp["tau"]), acts

        xh, yh = self._half(x, y)
        g = jax.vmap(lambda c, xi, yi: jax.grad(
            lambda c: loss(c, xi, yi)[0])(c))(cp, xh, yh)
        acts = jax.vmap(lambda c, xi: client_forward(c["c"], xi))(cp, x)
        t = t + 1
        out = jax.vmap(lambda p, g, m, v, tt: adam_tree(p, g, m, v, tt,
                                                        hp["lr"]))(
            cp, g, mu, nu, t)
        return out[0], out[1], out[2], t, acts, g

    def _global_step(self, sp, s_mu, s_nu, s_t, masks, m_mu, m_nu, m_t,
                     acts, y, idx):
        hp = self.hp
        S = idx.shape[0]
        m_sel = jax.tree.map(lambda l: l[idx], masks)
        a_sel, y_sel = self._half(acts[idx], y[idx])

        def loss(sp, m_sel):
            ces = jax.vmap(lambda m, a, yy: cross_entropy(
                server_forward(sp, jax.tree.map(
                    lambda g: jnp.broadcast_to(g, (a.shape[0],) + g.shape),
                    m), a), yy))(m_sel, a_sel, y_sel)
            leaves_ = jax.tree.leaves(m_sel)
            l1 = sum(jnp.sum(jnp.abs(l.astype(jnp.float32)))
                     for l in leaves_) / sum(l.size for l in leaves_)
            return jnp.sum(ces) + hp["lam"] * l1 * S, ces

        (_, ces), (g_sp, g_m) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(sp, m_sel)
        g_sp = jax.tree.map(lambda g: g / S, g_sp)
        s_t = s_t + 1
        sp, s_mu, s_nu = adam_tree(sp, g_sp, s_mu, s_nu, s_t, hp["lr"])
        mt = m_t[idx] + 1
        sel = lambda l: l[idx]
        new_m, new_mu, new_nu = jax.vmap(
            lambda p, g, m, v, tt: adam_tree(p, g, m, v, tt, hp["lr"]))(
            m_sel, g_m, jax.tree.map(sel, m_mu), jax.tree.map(sel, m_nu), mt)
        put = lambda full, part: jax.tree.map(
            lambda f, p: f.at[idx].set(p.astype(f.dtype)), full, part)
        return (sp, s_mu, s_nu, s_t, put(masks, new_m), put(m_mu, new_mu),
                put(m_nu, new_nu), m_t.at[idx].set(mt), ces, g_sp, g_m)

    def _make_round(self, global_phase: bool):
        gamma = self.hp["gamma"]
        prec = jax.default_matmul_precision(self.precision)

        def round_fn(state, xs, ys, keys, forced, use_forced):
            with prec:
                return _round(state, xs, ys, keys, forced, use_forced)

        def _round(state, xs, ys, keys, forced, use_forced):
            state = dict(state, ucb=ucb_new_round(state["ucb"], gamma))

            def body(carry, inp):
                st, g1 = carry
                x, y, key, fsel, i = inp
                cp, mu, nu, t, acts, g_c = self._client_step(
                    st["cp"], st["c_mu"], st["c_nu"], st["c_t"],
                    x.astype(self.dtype), y)
                st = dict(st, cp=cp, c_mu=mu, c_nu=nu, c_t=t)
                keep = lambda old, new: jax.tree.map(
                    lambda o, n: jnp.where(i == 0, n.astype(o.dtype), o),
                    old, new)
                if not global_phase:
                    return (st, (keep(g1[0], g_c), g1[1])), None
                scores = ucb_scores(st["ucb"], key)
                _, own = jax.lax.top_k(scores, self.k)
                idx = jnp.where(use_forced, fsel, jnp.sort(own))
                (sp, s_mu, s_nu, s_t, masks, m_mu, m_nu, m_t, ces, g_sp,
                 g_m) = self._global_step(
                    st["sp"], st["s_mu"], st["s_nu"], st["s_t"],
                    st["masks"], st["m_mu"], st["m_nu"], st["m_t"],
                    acts, y, idx)
                sel = jnp.zeros((self.n,), jnp.float32).at[idx].set(1.0)
                dense = jnp.zeros((self.n,), jnp.float32).at[idx].set(ces)
                ucb = ucb_update(st["ucb"], sel, dense, gamma)
                st = dict(st, sp=sp, s_mu=s_mu, s_nu=s_nu, s_t=s_t,
                          masks=masks, m_mu=m_mu, m_nu=m_nu, m_t=m_t,
                          ucb=ucb)
                g1 = (keep(g1[0], g_c), keep(g1[1], (g_sp, g_m)))
                return (st, g1), (idx, ces, scores)

            T = xs.shape[0]
            g1 = (_zeros(state["cp"]), (_zeros(state["sp"]), jax.tree.map(
                lambda l: jnp.zeros((self.k,) + l.shape[1:], jnp.float32),
                state["masks"])))
            (state, g1), outs = jax.lax.scan(
                body, (state, g1), (xs, ys, keys, forced, jnp.arange(T)))
            return state, g1, outs

        return round_fn

    def _state(self):
        return {"cp": self.cp, "c_mu": self.c_mu, "c_nu": self.c_nu,
                "c_t": self.c_t, "sp": self.sp, "s_mu": self.s_mu,
                "s_nu": self.s_nu, "s_t": self.s_t, "masks": self.masks,
                "m_mu": self.m_mu, "m_nu": self.m_nu, "m_t": self.m_t,
                "ucb": self.ucb}

    def _set(self, st):
        for k, v in st.items():
            setattr(self, k, v)

    def snapshot(self):
        """The trainables and Adam's second moments, by leaf."""
        return {"params": leaves({"client": self.cp["c"],
                                  "proj": self.cp["p"], "server": self.sp,
                                  "masks": self.masks}),
                "nu": leaves({"client": self.c_nu["c"],
                              "proj": self.c_nu["p"], "server": self.s_nu,
                              "masks": self.m_nu})}

    def run_round(self, xs, ys, global_phase: bool, forced=None):
        """One round on staged (T, C, B, ...) data.  ``forced``: (T, k)
        selections to follow, or None to select itself.  Returns, per
        global iteration, (selected ids, their CE, every client's
        score)."""
        T = xs.shape[0]
        keys = jnp.stack([jax.random.fold_in(self.key, self.n_selects + t)
                          for t in range(T)])
        use = forced is not None
        fsel = jnp.asarray(forced if use else np.zeros((T, self.k)),
                           jnp.int32)
        st, (first_c, first_s), glob = self._round[global_phase](
            self._state(), jnp.asarray(xs), jnp.asarray(ys), keys, fsel,
            jnp.asarray(use))
        self._set(st)
        if not self.first_grads:
            self.first_grads.update(
                {k: float(np.linalg.norm(v)) for k, v in leaves(
                    {"client": first_c["c"], "proj": first_c["p"]}).items()})
        if not global_phase:
            return []
        self.n_selects += T
        idx, ces, scores = jax.device_get(glob)
        if not any(k.startswith("['server']") for k in self.first_grads):
            g_sp, g_m = first_s
            self.first_grads.update(
                {k: float(np.linalg.norm(v)) for k, v in leaves(
                    {"server": g_sp, "masks": g_m}).items()})
        return [(idx[t], ces[t], scores[t]) for t in range(T)]


def stage_round(data, batch: int, rng: np.random.Generator):
    """One round's batches as (T, C, B, ...) arrays, in the order the
    trainer draws them: one shuffle per client, clients in order, each
    epoch cut into whole batches."""
    perms = [rng.permutation(len(d[0])) for d in data]
    T = min(len(d[0]) for d in data) // batch
    xs = np.stack([np.stack([d[0][p[t * batch:(t + 1) * batch]]
                             for d, p in zip(data, perms)])
                   for t in range(T)])
    ys = np.stack([np.stack([d[1][p[t * batch:(t + 1) * batch]]
                             for d, p in zip(data, perms)])
                   for t in range(T)])
    return xs, ys


def run_protocol(proto: Protocol, data, hp: dict, rng, n_steps: int, *,
                 forced: Optional[List[List]] = None, snapshot_at=(1, 3)):
    """``n_steps`` calls of the protocol (each ``hp['rounds']`` rounds, the
    first ``round(kappa * rounds)`` local).  Returns (per step: the global
    iterations' (ids, CE, scores); {step: snapshot})."""
    R = hp["rounds"]
    local = int(round(hp["kappa"] * R))
    steps, snaps = [], {}
    for s in range(n_steps):
        its = []
        for r in range(R):
            xs, ys = stage_round(data, hp["batch_size"], rng)
            g = r >= local
            f = None
            if g and forced is not None:
                T = xs.shape[0]
                f = np.stack([np.asarray(forced[s][len(its) + t][0])
                              for t in range(T)])
            its += proto.run_round(xs, ys, g, forced=f)
        steps.append(its)
        if s + 1 in snapshot_at:
            snaps[s + 1] = proto.snapshot()
    return steps, snaps

