"""w_build_ms: device milliseconds per round under the program scope
``fl.w_build`` (``core/server.py`` ``_local_phase``: the clients' updated
pytrees flattened into the (C, D) matrix W, and an attack's transform of
it), over the rounds of the traced window (``harness/scopes.py``).  XLA
writes W's concatenate in place, leaf by leaf, behind layout copies of
the leaves; those ops carry no name stack and count here by the one their
consumers share (``harness/xplane_meta.py`` ``consumer_names``).  Moves
``rounds_per_s``.
"""
from harness import scopes

SCOPE = "fl.w_build"


def read(ctx):
    secs = scopes.of_run(ctx).scope(SCOPE)
    return None if secs is None else 1e3 * secs / ctx["rounds"]
