"""Model FLOP/s utilisation of serving: the prompt tokens prefilled and
the tokens decoded in the window, each costing two FLOPs per matmul
weight plus its attention over its context (``bench.flops``), over the
window, chips and the chip's bf16 peak, in %."""
from bench import flops


def read(run):
    c, cfg, win = run["counts"], run["config"], run["window_s"]
    steps = c.get("decode_active") or []
    if win <= 0 or not steps:
        return None
    per_weight = 2.0 * flops.lm_matmul_params(cfg)
    att = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * cfg["num_hidden_layers"]
    dec = sum(n * per_weight for n in steps) + att * sum(c["decode_kv_tokens"])
    total = c["prompt_flops"] + dec
    return 100.0 * total / (win * run["chips"]
                            * run["peaks"]["flops_bf16_per_s"])
