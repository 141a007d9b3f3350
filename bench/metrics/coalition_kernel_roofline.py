"""coalition_kernel_roofline: the Pallas coalition kernels' share of their
HBM roofline, in percent.

The bytes one round needs from (C, D, K), whatever implements it
(``harness/counts.coalition_round_bytes``: two reads of the unpadded f32 W
and one write of the K barycenter rows and the θ row), over 819 GB/s, set
against the summed device time of the two kernels' events in the trace
(``harness/counts.COALITION_KERNELS``; a round calls each once).  A traced
window without them fails the run.  The round is bound by memory, not by
operations (its FLOPs are ~3 per byte).  Moves ``rounds_per_s``.
"""
from harness import counts


def read(ctx):
    secs, rounds = ctx["trace"].kernel_per_run(counts.COALITION_KERNELS)
    cfg = ctx["config"]
    d = counts.cnn_params(cfg["model"])
    need = counts.coalition_round_bytes(cfg["n_clients"], d,
                                        cfg["n_coalitions"])
    least = need * rounds / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
