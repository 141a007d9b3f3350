"""coalition_kernel_ms: device milliseconds per round of the two Pallas
coalition kernels (``harness/counts.COALITION_KERNELS``), summed from their
events in the trace.  A traced window without them fails the run.  Moves
``rounds_per_s``.
"""
from harness import counts


def read(ctx):
    secs, rounds = ctx["trace"].kernel_per_run(counts.COALITION_KERNELS)
    return 1e3 * secs / rounds
