"""Device time per decode step of the block-checksum program (the
engine's jitted ``_block_sums_fn``, run over the whole physical pool
before each gather and after each write), from the trace. Moves
``serve_tokens_per_s``."""
MODULE = "_block_sums_fn"  # its program, as the trace names it


def read(r):
    if r.trace is None or not r.facts.get("steps"):
        return None
    ns = sum(v for k, v in r.trace.module_ns.items() if MODULE in k)
    return ns * 1e-6 / r.facts["steps"] if ns else None
