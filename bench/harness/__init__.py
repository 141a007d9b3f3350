"""The on-chip benchmark's shared machinery.

Everything here is the yardstick: device checks and the peaks table, the
compile counter, the synthetic inputs, the plain references, the trace
reduction, the operation and byte counts, and the comparison that decides
``correct``.  It imports the program under test (``repro``) only in the
drivers, one per traffic kind (:mod:`harness.train`), which build the
timed path exactly as its own entry points build it.
"""
