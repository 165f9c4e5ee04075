"""Operations and bytes of the dropless MoE layers' grouped matmul, from
the configuration's sizes (``bench/configs/granite-4.0-h-small.json``
keys), never from the program. Counted as in ``cost.py``: a multiply-add
is 2 FLOPs, a byte is counted once per pass that must move it through
HBM."""
from __future__ import annotations


def gmm_call(c: dict, rows: int) -> tuple:
    """(FLOPs, bytes) of one MoE layer's grouped matmul over the experts
    held here, ``rows`` token-expert assignments routed to them: the gated
    expert (``silu(x W_in) * (x W_gate)) W_out``, three ``hidden_size`` x
    ``intermediate_size`` matrices an expert) on every row; every held
    expert's weights read once in bf16, and the rows in and out in bf16
    (the intermediate can stay on chip)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    held = c["num_local_experts"]
    flops = 2 * 3 * d * f * rows
    byts = held * 3 * d * f * 2 + 2 * rows * d * 2
    return flops, byts


def held_rows(r, name: str = "serve.moe_held") -> list:
    """Rows routed to held experts in each MoE layer call of the traced
    window, from the program's notes (``repro.obs.note``, taken with each
    call's token pull); empty when the program keeps no such notes."""
    run = r.run
    if r.trace is None or run.setup_s is None or run.window_s is None:
        return []
    from repro import obs
    read = getattr(obs, "profiled_notes", None)
    if read is None:
        return []
    lo = run.t_start + run.setup_s
    return [n for key, _, layers in read(lo, lo + run.window_s)
            if key == name for n in layers]
