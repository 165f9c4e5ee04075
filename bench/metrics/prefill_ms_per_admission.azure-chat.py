"""Mean length of one admission prefill in the Azure-shaped chat cell: the
traced window's ``serve.prefill`` spans (batch-1 prefill through every
layer, the pool scatter and SSD state write, checksum refresh,
first-token pull; the program's own ``repro.obs.span``). An admission runs
inside a scheduler step, before its decode, so it lengthens the gap of
every running request. Moves ``itl_p95_ms``."""
from benchlib import spans


def read(r):
    lengths = [b - a for n, a, b in spans.profiled(r) or []
               if n == "serve.prefill"]
    return 1e3 * sum(lengths) / len(lengths) if lengths else None
