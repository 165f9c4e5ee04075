"""Share of the traced window in which no operation ran on the device,
in a training cell. Moves ``train_tokens_per_s``."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * r.trace.idle_share
