"""device_idle_share.train: share of the traced training window in which
no operation ran on the chip, in percent.

1 - (union of device-op intervals) / (traced window), from the profiler
trace (``harness/trace.py``).  Host gaps at chunk boundaries (snapshot
publish, history copies) and between small ops inside the scanned rounds
both count.  Moves ``rounds_per_s``.
"""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    t = ctx["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s)
