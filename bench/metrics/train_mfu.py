"""Model FLOP utilization of the training window: the benchmark's own
FLOPs per token (bench/flops.py) times the tokens completed, over the
window, the chips and the chip's bf16 peak, in percent."""


def read(r):
    if not r.get("tokens"):
        return None
    rate = r["tokens"] / r["window_s"]
    return 100.0 * r["flops_per_token"] * rate / (
        r["chips"] * r["peak"]["bf16_flops_per_s"])
