"""edge_idle_ms: device-idle milliseconds per federation run while the
host is in the run's start-up and hand-over: the host spans
``fl.cohort_schedule``, ``fl.prologue`` (round 0's call) and
``fl.history`` (the closing concatenate and device-to-host read of the
trace) (``core/server.py`` ``_run_driver``), split by overlap as in
``harness/scopes.py``, over the ``fl.run`` spans in the window.  Moves
``rounds_per_s``.
"""
from harness import scopes

SPANS = ("fl.cohort_schedule", "fl.prologue", "fl.history")


def read(ctx):
    red = scopes.of_run(ctx)
    secs = red.idle(SPANS)
    if secs is None:
        return None
    return 1e3 * secs / red.span_counts.get(scopes.RUN_SPAN, 1)
