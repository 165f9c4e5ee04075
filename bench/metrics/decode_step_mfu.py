"""The whole decode step's share of the chip's roofline: the least time
one step needs (every weight read once in bf16 plus the live packed KV,
or the matmul FLOPs of the batch, whichever bound holds; from
``benchlib.cost``) over the mean time per scheduler step of the traced
window, on the profiler's clock. Checksums, idle slots and host time are
not work a step needs, so they lower the share. Moves
``serve_tokens_per_s``."""
from benchlib import cost


def read(r):
    f = r.facts
    if r.trace is None or r.peaks is None or not f.get("steps"):
        return None
    flops, byts = cost.decode_step(r.config, f["container"], f["slots"],
                                   f["ctx_total_mean"])
    least, _ = cost.roofline_time(flops, byts, r.peaks)
    return 100.0 * least / (r.trace.window_ns * 1e-9 / f["steps"])
