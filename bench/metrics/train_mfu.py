"""Model FLOP/s utilisation of the training step: the forward and backward
FLOPs per token that the model's shapes require (no recomputation, from
``benchlib.cost``), times the tokens of the steps in the traced window
over the window's length on the profiler's clock, over the chip's bf16
peak. Moves ``train_tokens_per_s``."""


def read(r):
    f = r.facts
    if r.trace is None or r.peaks is None or not f.get("steps"):
        return None
    tokens_per_s = f["steps"] * f["tokens_per_step"] / (
        r.trace.window_ns * 1e-9)
    return 100.0 * tokens_per_s * f["flops_per_token"] / (
        r.peaks.bf16_flops * r.run.cell.chips)
