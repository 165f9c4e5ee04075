"""Mean wall time of one admission prefill in the window (batch-1
prefill, scatter into the pool, checksum refresh, first-token pull), from
the program's ``serve_prefill_seconds`` sum and count. An admission runs
inside a scheduler step, before its decode, so it lengthens the gap of
every running request. Moves ``itl_p95_ms``."""


def read(r):
    s, n = r.facts.get("registry", {}).get("serve_prefill_seconds", (0, 0))
    return 1e3 * s / n if n else None
