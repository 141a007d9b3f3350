"""local_phase_ms: device milliseconds per round under the program scope
``fl.local_phase`` (``core/server.py`` ``_local_phase``: the cohort
gather, poisoning and the vmapped client update, every local SGD step),
over the rounds of the traced window, round 0 included
(``harness/scopes.py``).  Moves ``rounds_per_s``.
"""
from harness import scopes

SCOPE = "fl.local_phase"


def read(ctx):
    secs = scopes.of_run(ctx).scope(SCOPE)
    return None if secs is None else 1e3 * secs / ctx["rounds"]
