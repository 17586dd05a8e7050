"""Plain reference of personalised serving on a Qwen2-style decoder.

Qwen2 (arXiv:2407.10671) layer: RMSNorm, grouped-query attention with
biased q/k/v projections and rotary positions (half-split, base
``rope_theta``), residual; RMSNorm, SwiGLU MLP, residual; final RMSNorm
and the output head.  AdaSplit personalises the server's layers (those
from ``split_layer`` on) with a client's unit masks: one gate per
attention head, on the head's output before the output projection, and
one per MLP hidden unit.  Departure from the model card, as the program
has it: the output head is its own table (the card ties it to the
embedding), since tying would hand server weights to the clients.

The forward runs the whole sequence at once, causal, in float32 at full
matmul precision, and returns logits only at the positions asked for.
``quant="fp8"`` is the control, one precision below the served
bfloat16: every place the served model keeps bfloat16 (weights, the
inputs of every projection, the residual stream) is rounded to float8
e4m3 with a scale per row (weights: per output column) instead.

Weights use the program's layout (client and server stacks of layers
with a leading layer axis) so the benchmark can hand the same ones to
both; :func:`init_params` makes them from a key, :func:`mask_rows`
makes any client's gates from a key.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _n_split(cfg: dict) -> int:
    return max(1, int(round(cfg["mu"] * cfg["num_hidden_layers"])))


def padded_vocab(cfg: dict, multiple: int = 256) -> int:
    """Rows of the embedding and output tables: the vocabulary rounded up
    to a multiple of 256, as the program lays them out (the padding rows
    never win: their logits are not read)."""
    return -(-cfg["vocab_size"] // multiple) * multiple


def init_params(cfg: dict, key, *, served_dtype="bfloat16"):
    """Every weight, in the program's serving layout: matmul weights of
    64Ki elements or more in the served type, the rest (norm scales,
    biases) in float32.  Jit it: one call on the device."""
    d, hq, hkv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, f = cfg["head_dim"], cfg["intermediate_size"]
    v = padded_vocab(cfg)
    s = _n_split(cfg)
    keys = iter(jax.random.split(key, 64))
    dt = jnp.dtype(served_dtype)

    def big(shape, scale):
        x = jax.random.normal(next(keys), shape, jnp.float32) * scale
        return x.astype(dt) if np.prod(shape) >= (1 << 16) and \
            len(shape) >= 2 else x

    def small(shape, scale, base=0.0):
        return base + scale * jax.random.normal(next(keys), shape,
                                                jnp.float32)

    def stack(n):
        return [[{
            "norm1": {"scale": small((n, d), 0.1, 1.0)},
            "mixer": {"wq": big((n, d, hq * hd), d ** -0.5),
                      "wk": big((n, d, hkv * hd), d ** -0.5),
                      "wv": big((n, d, hkv * hd), d ** -0.5),
                      "wo": big((n, hq * hd, d), (hq * hd) ** -0.5),
                      "bq": small((n, hq * hd), 0.1),
                      "bk": small((n, hkv * hd), 0.1),
                      "bv": small((n, hkv * hd), 0.1)},
            "norm2": {"scale": small((n, d), 0.1, 1.0)},
            "ffn": {"w_gate": big((n, d, f), d ** -0.5),
                    "w_up": big((n, d, f), d ** -0.5),
                    "w_down": big((n, f, d), f ** -0.5)}}]]

    return {"client": {"embed": {"table": big((v, d), 1.0)},
                       "segments": stack(s)},
            "server": {"final_norm": {"scale": small((d,), 0.1, 1.0)},
                       "segments": stack(cfg["num_hidden_layers"] - s),
                       "lm_head": {"table": big((v, d), d ** -0.5)}}}


def mask_rows(cfg: dict, key, clients, keep: float):
    """Unit masks of the given clients, (len(clients), server layers, U),
    each row drawn from the client's id alone: 1 with probability
    ``keep``, else 0.  Returns {"mixer": ..., "ffn": ...}."""
    n = cfg["num_hidden_layers"] - _n_split(cfg)

    def row(c):
        k = jax.random.fold_in(key, c)
        km, kf = jax.random.split(k)
        return {"mixer": (jax.random.uniform(
                    km, (n, cfg["num_attention_heads"])) < keep
                    ).astype(jnp.float32),
                "ffn": (jax.random.uniform(
                    kf, (n, cfg["intermediate_size"])) < keep
                    ).astype(jnp.float32)}
    return jax.vmap(row)(jnp.asarray(clients, jnp.int32))


# ---------------------------------------------------------------------------


def fp8(x, axis=-1):
    """``x`` rounded to float8 (e4m3) with one scale per slice along
    ``axis`` (its largest magnitude maps to the format's largest, 448)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs            # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layers(params):
    """Client then server layers as one stack with a leading layer axis."""
    c = params["client"]["segments"][0][0]
    s = params["server"]["segments"][0][0]
    return jax.tree.map(lambda a, b: jnp.concatenate([a, b]), c, s)


def logits_at(cfg: dict, params, tokens, where, gates, *, quant=None):
    """Logits (len(where), vocab) of one sequence ``tokens`` (S,) at the
    positions ``where``; ``gates`` {"mixer": (server layers, H), "ffn":
    (server layers, F)}.  Params are taken in float32."""
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    if quant == "fp8":
        mm = lambda a, b: fp8(a) @ fp8(b, axis=-2)
        keep = fp8
    else:
        mm = lambda a, b: a @ b
        keep = lambda a: a
    S = tokens.shape[0]
    pos = jnp.arange(S)
    n_client = _n_split(cfg)
    g_mix = jnp.concatenate([jnp.ones((n_client, hq)), gates["mixer"]])
    g_ffn = jnp.concatenate([jnp.ones((n_client, cfg["intermediate_size"])),
                             gates["ffn"]])
    causal = pos[None, :] <= pos[:, None]

    def layer(x, lp):
        p, gm, gf = lp
        h = _rms(x, p["norm1"]["scale"], eps)
        a = p["mixer"]
        q = (mm(h, a["wq"]) + a["bq"]).reshape(S, hq, hd)
        k = (mm(h, a["wk"]) + a["bk"]).reshape(S, hkv, hd)
        v = (mm(h, a["wv"]) + a["bv"]).reshape(S, hkv, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        o = o * gm[None, :, None]
        x = keep(x + mm(o.reshape(S, hq * hd), a["wo"]))
        h = _rms(x, p["norm2"]["scale"], eps)
        fp = p["ffn"]
        u = jax.nn.silu(mm(h, fp["w_gate"])) * mm(h, fp["w_up"])
        return keep(x + mm(u * gf, fp["w_down"])), None

    with jax.default_matmul_precision("highest"):
        x = keep(p32["client"]["embed"]["table"][tokens])
        x, _ = jax.lax.scan(layer, x, (_layers(p32), g_mix, g_ffn))
        x = _rms(x[where], p32["server"]["final_norm"]["scale"], eps)
        head = p32["server"]["lm_head"]["table"][:cfg["vocab_size"]]
        return mm(x, head.T)
