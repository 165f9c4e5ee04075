"""Share of the roofline reached by the paged packed-KV decode attention
kernel: the least time its work needs (the live packed K and V of every
slot, q in and the output out; or its FLOPs, whichever bound holds; from
``benchlib.cost``), one call per layer and decode step, over the kernel's
device time in the traced window. Moves ``serve_tokens_per_s``."""
from benchlib import cost
from benchlib.trace import matcher

KERNEL = matcher("paged_flash_decode")  # kernels/packed_flash_decode.py


def read(r):
    f = r.facts
    if r.trace is None or r.peaks is None or not f.get("steps"):
        return None
    ns = r.trace.time_ns(KERNEL)
    if not ns:
        return None
    flops, byts = cost.paged_decode_call(r.config, f["container"], f["slots"],
                                         f["ctx_total_mean"])
    least, _ = cost.roofline_time(flops, byts, r.peaks)
    calls = f["steps"] * r.config["num_hidden_layers"]
    return 100.0 * calls * least / (ns * 1e-9)
