"""Share of the traced window in which no operation ran on the device,
in the chat serving cell. Moves ``itl_p95_ms``."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * r.trace.idle_share
