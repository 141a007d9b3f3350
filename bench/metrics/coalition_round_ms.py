"""coalition_round_ms: device milliseconds per round under the program
scope ``fl.coalition_round`` (``core/server.py`` ``_aggregate``: the
strategy's round over W, both W passes, barycenters, medoids and θ, and θ
unflattened), over the rounds of the traced window, whichever backend
runs it (``harness/scopes.py``).  Moves ``rounds_per_s``.
"""
from harness import scopes

SCOPE = "fl.coalition_round"


def read(ctx):
    secs = scopes.of_run(ctx).scope(SCOPE)
    return None if secs is None else 1e3 * secs / ctx["rounds"]
