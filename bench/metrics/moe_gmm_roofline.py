"""Share of the roofline reached by the grouped matmul of the dropless MoE
layers (``jax.lax.ragged_dot`` over the experts held here; on the chip its
``ragged-dot`` custom calls): for each MoE layer call in the traced window,
the least time its work needs (every held expert's weights read once in
bf16 plus the routed rows in and out, or the FLOPs of the rows routed to
held experts, whichever bound holds; ``benchlib.moe_cost``), summed, over
those ops' device time. The rows of each call are the program's count of
assignments on held experts, pulled with the call's tokens. Moves
``itl_p95_ms``."""
from benchlib import cost, moe_cost
from benchlib.trace import matcher

KERNEL = matcher("ragged-dot")  # models/moe.py moe_dropless


def read(r):
    if r.trace is None or r.peaks is None:
        return None
    ns = r.trace.time_ns(KERNEL)
    rows = moe_cost.held_rows(r)
    if not ns or not rows:
        return None
    least = sum(cost.roofline_time(*moe_cost.gmm_call(r.config, n),
                                   r.peaks)[0] for n in rows)
    return 100.0 * least / (ns * 1e-9)
