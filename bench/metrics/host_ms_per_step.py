"""Host time per scheduler step outside prefill and the decode call
(admission bookkeeping, checksum verify/refresh pulls, token replay and
callbacks), from the program's ``serve_step_seconds``,
``serve_decode_seconds`` and ``serve_prefill_seconds`` sums over the
window's steps. Moves ``itl_p95_ms``."""


def read(r):
    reg = r.facts.get("registry", {})
    step_s, steps = reg.get("serve_step_seconds", (0, 0))
    if not steps:
        return None
    rest = (reg.get("serve_decode_seconds", (0, 0))[0]
            + reg.get("serve_prefill_seconds", (0, 0))[0])
    return 1e3 * (step_s - rest) / steps
