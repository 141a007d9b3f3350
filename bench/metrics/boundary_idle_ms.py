"""boundary_idle_ms: device-idle milliseconds per chunk boundary while the
host is in the engine's boundary work: the host spans ``fl.dispatch`` (a
chunk program's call), ``fl.publish`` (a snapshot's device-to-host read
and write), ``fl.checkpoint`` and ``fl.emit`` (``core/server.py``
``_run_driver``).  Each idle gap is split over the innermost spans by
overlap (``harness/scopes.py``).  A boundary is round 0 or a chunk's end:
one more than the ``fl.dispatch`` spans in the window.  Moves
``rounds_per_s``.
"""
from harness import scopes

SPANS = ("fl.dispatch", "fl.publish", "fl.checkpoint", "fl.emit")


def read(ctx):
    red = scopes.of_run(ctx)
    secs = red.idle(SPANS)
    if secs is None:
        return None
    return 1e3 * secs / (red.span_counts.get("fl.dispatch", 0) + 1)
