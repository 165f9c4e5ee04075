"""Host time per scheduler step with no device call pending, in the
Azure-shaped chat cell: the mean over the traced window's ``serve.step``
spans of each one's length less the union of its ``serve.decode``,
``serve.prefill`` and ``serve.checksums`` spans (the program's own
``repro.obs.span`` phases). Moves ``itl_p95_ms``."""
from benchlib import spans


def read(r):
    return spans.mean_step_host_ms(r)
