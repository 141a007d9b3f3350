"""eval_ms: device milliseconds per round under the program scope
``fl.eval`` (``core/server.py`` ``_eval``: the in-round eval of θ on the
test set), over the rounds of the traced window (``harness/scopes.py``).
Moves ``rounds_per_s``.
"""
from harness import scopes

SCOPE = "fl.eval"


def read(ctx):
    secs = scopes.of_run(ctx).scope(SCOPE)
    return None if secs is None else 1e3 * secs / ctx["rounds"]
