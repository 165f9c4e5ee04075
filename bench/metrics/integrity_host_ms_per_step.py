"""Host-clock time of the pool's integrity checks per scheduler step: the
traced window's ``serve.verify`` and ``serve.refresh`` spans (checksum
program, its pull and the host compare or record), over its
``serve.step`` spans. The device-only share of the same work is
``integrity_ms_per_step``. Moves ``serve_tokens_per_s``."""
from benchlib import spans


def read(r):
    return spans.integrity_host_ms(r)
