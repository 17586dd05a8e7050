"""Model FLOP/s utilisation of split training: the FLOPs the protocol's
completed rounds require (``bench.flops.lenet_round_flops``: every
client's step, and the selected clients' server step in global rounds,
forward plus backward) over the window, chips and the chip's bf16 peak,
in %."""


def read(run):
    f, win = run["counts"].get("train_flops"), run["window_s"]
    if not f or win <= 0:
        return None
    return 100.0 * f / (win * run["chips"] * run["peaks"]["flops_bf16_per_s"])
