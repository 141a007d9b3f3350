"""compile_s: backend-compile seconds of the run's set-up.

Read from ``jax.monitoring``'s backend-compile events (the copy of the
compile counter in ``harness/compile_stats.py``), summed from process start
to the first timed round or batch.  With the persistent cache warm it is
the compiles that the cache did not serve.  Moves ``setup_s``.
"""


def read(ctx):
    return ctx["setup_compile_s"]
