"""Operations and bytes from shapes: the benchmark's own arithmetic.

Everything here is computed from a configuration file's numbers and the
traffic's counts, never read from the program, so that a change to the
program cannot move the yardstick.  FLOPs count a multiply-add as two;
bytes count each operand read and each result written once, at its
logical (unpadded) size, which makes ``max(flops / peak, bytes / bw)`` a
lower bound on a call's time.
"""
from __future__ import annotations

import json
import math
import re
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, pk: dict) -> float:
    """Least time a call can take on the chip: the larger of its compute
    and its memory bound."""
    return max(flops / pk["flops_bf16_per_s"], nbytes / pk["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# LeNet split training (paper §4.4 backbone)
# ---------------------------------------------------------------------------


def lenet_split(cfg: dict) -> int:
    """Number of conv blocks the client owns."""
    return max(1, int(round(cfg["mu"] * len(cfg["conv_channels"]))))


def lenet_blocks(cfg: dict):
    """Per conv block: (input height = width, Cin, Cout).  Each block is
    a same-padded KxK conv then a 2x2 max-pool."""
    hw, cin, out = cfg["image_size"], 3, []
    for c in cfg["conv_channels"]:
        out.append((hw, cin, c))
        cin, hw = c, hw // 2
    return out


def lenet_acts_dim(cfg: dict) -> int:
    s = lenet_split(cfg)
    hw, _, c = lenet_blocks(cfg)[s - 1]
    return (hw // 2) ** 2 * c


def _conv_flops(hw, cin, cout, k):
    return 2 * hw * hw * k * k * cin * cout


def lenet_client_fwd_flops(cfg: dict) -> float:
    """Client tower plus projection head and the NT-Xent similarity, per
    example, forward only."""
    s, k = lenet_split(cfg), cfg["kernel"]
    f = sum(_conv_flops(hw, ci, co, k) for hw, ci, co in lenet_blocks(cfg)[:s])
    d, h, p = lenet_acts_dim(cfg), cfg["proj_hidden"], cfg["proj_dim"]
    f += 2 * (d * h + h * p)
    f += 2 * cfg["hparams"]["batch_size"] * p       # q @ q.T, per row
    return float(f)


def lenet_server_fwd_flops(cfg: dict) -> float:
    s, k = lenet_split(cfg), cfg["kernel"]
    blocks = lenet_blocks(cfg)
    f = sum(_conv_flops(hw, ci, co, k) for hw, ci, co in blocks[s:])
    hw_out = max(blocks[-1][0] // 2, 1)
    flat = hw_out ** 2 * cfg["conv_channels"][-1]
    f += 2 * (flat * cfg["fc_hidden"] + cfg["fc_hidden"] * cfg["d_model"]
              + cfg["d_model"] * cfg["n_classes"])
    return float(f)


def lenet_round_flops(cfg: dict, *, clients: int, iters: int, batch: int,
                      selected: int, global_phase: bool) -> float:
    """Training FLOPs one protocol round requires: every client's local
    step, and in the global phase the selected clients' server step.
    Forward plus backward is three forwards; nothing recomputed counts."""
    f = 3.0 * lenet_client_fwd_flops(cfg) * clients * iters * batch
    if global_phase:
        f += 3.0 * lenet_server_fwd_flops(cfg) * selected * iters * batch
    return f


# ---------------------------------------------------------------------------
# Kernels, from the operand shapes a trace event names
# ---------------------------------------------------------------------------

_SHAPE = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64)\[([0-9,]*)\]")


_ATTRS = re.compile(r", (custom_call_target|metadata|backend_config|"
                    r"operand_layout_constraints|frontend_attributes)=")


def hlo_shapes(text: str):
    """Every ``dtype[dims]`` array shape of an HLO instruction, in order
    (result first, then operands), as (dtype, dims); the attributes after
    the operands are left out."""
    out = []
    for dt, dims in _SHAPE.findall(_ATTRS.split(text, 1)[0]):
        out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def shape_bytes(shape) -> int:
    dt, dims = shape
    return DTYPE_BYTES[dt] * math.prod(dims)


def gemm_cost(a_dims, b_dims, out_dims, itemsize: int = 4):
    """A batched GEMM (..., M, K) @ (..., K, N): FLOPs and the bytes of
    its operands and result."""
    *lead, m, k = a_dims
    n = b_dims[-1]
    flops = 2.0 * math.prod(lead) * m * k * n
    nbytes = itemsize * (math.prod(a_dims) + math.prod(b_dims)
                         + math.prod(out_dims))
    return flops, float(nbytes)


def lenet_conv_panels(cfg: dict):
    """(K*K*Cin, Cout) of every conv block: the GEMM each block is."""
    k = cfg["kernel"]
    return [(k * k * ci, co) for _, ci, co in lenet_blocks(cfg)]


def _pad128(n: int) -> int:
    return -(-n // 128) * 128


def panel_gemm_cost(text: str, panels):
    """One ``panel_gemm`` call from its HLO text, at its logical size.

    The kernel runs on operands padded to 128 along K and N: result
    (..., M, Np), operands (..., M, Kp) and (..., Kp, Np), with leading
    batch axes (clients, and any ``vmap`` over them; the filter panel's
    may be fewer when it is shared).  The conv block it serves is the
    one in ``panels`` ((K, N) per block) whose K pads to Kp; FLOPs and
    bytes are that GEMM's own, so the padding counts as waste.  Returns
    (flops, bytes), or None when no block matches."""
    shapes = [s for s in hlo_shapes(text) if len(s[1]) >= 3]
    if len(shapes) < 3:
        return None
    a, b = shapes[1], shapes[2]
    *lead_a, m, kp = a[1]
    lead_b = b[1][:-2]
    match = [(k, n) for k, n in panels if _pad128(k) == kp]
    if not match or b[1][-2] != kp:
        return None
    k, n = match[0]
    ba, bb = math.prod(lead_a), math.prod(lead_b)
    item = DTYPE_BYTES[a[0]]
    flops = 2.0 * ba * m * k * n
    nbytes = item * (ba * m * k + bb * k * n + ba * m * n)
    return flops, float(nbytes)


def elementwise_cost(long_name: str, flops_per_element: float = 0.0):
    """A memory-bound kernel (fused Adam): bytes of every array named in
    its HLO text, results and operands alike; FLOPs per result element.
    Returns None when the text names no shapes."""
    shapes = hlo_shapes(long_name)
    if not shapes:
        return None
    n_out = math.prod(shapes[0][1]) if shapes[0][1] else 1
    return flops_per_element * n_out, float(sum(shape_bytes(s)
                                                for s in shapes))


# ---------------------------------------------------------------------------
# Decoder-only transformer serving (qwen2-0.5b)
# ---------------------------------------------------------------------------


def lm_layer_matmul_params(cfg: dict) -> int:
    d, hq, hkv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, f = cfg["head_dim"], cfg["intermediate_size"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def lm_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies through: every layer plus the output
    head (the input embedding is a row gather, not a matmul)."""
    return (cfg["num_hidden_layers"] * lm_layer_matmul_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


def lm_prefill_flops(cfg: dict, length: int) -> float:
    """A causal prefill of ``length`` tokens: token i attends i + 1 keys."""
    att = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * cfg["num_hidden_layers"] * length * (length + 1) / 2
    return 2.0 * lm_matmul_params(cfg) * length + att


def lm_kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * itemsize


def lm_weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Matmul weights one decode step must read once: every layer and the
    output head, at the served type."""
    return itemsize * lm_matmul_params(cfg)


def lm_decode_step_bytes(cfg: dict, kv_tokens: int, itemsize: int = 2) -> float:
    """Least bytes one batched decode step reads: the weights once, and
    the valid cached keys and values of every active slot."""
    return float(lm_weight_bytes(cfg, itemsize)
                 + kv_tokens * lm_kv_bytes_per_token(cfg, itemsize))
