"""The trace reduction on a hand-built trace, and on a small trace recorded
on a TPU v5e (``data/small_trace.xplane.pb``: three pallas fused rounds at
N = 10, D = 582,026, under the benchmark's own spans)."""
import os
from types import SimpleNamespace as NS

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "small_trace.xplane.pb")


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def fake(device_events, host_events):
    return NS(planes=[
        NS(name="/device:TPU:0",
           lines=[NS(name="XLA Modules", events=[ev("jit_f", 0, 10**9)]),
                  NS(name="XLA Ops", events=device_events)]),
        NS(name="/host:CPU", lines=[NS(name="python", events=host_events)]),
    ])


def test_busy_is_the_union_of_ops_clipped_to_the_window():
    ops = [ev("fusion.1", 50, 150), ev("fusion.2", 100, 200),   # overlap
           ev("center_sq_dists.1", 300, 400), ev("copy.3", 900, 1300)]
    host = [ev("bench.window", 100, 1000), ev("bench.federation_run", 100,
                                              500),
            ev("bench.publish", 500, 1000), ev("not_ours", 0, 2000)]
    red = trace.reduce_trace(fake(ops, host))
    assert red.window_s == pytest.approx(900e-9)
    # [100, 200] + [300, 400] + [900, 1000]
    assert red.busy_s == pytest.approx(300e-9)
    assert red.kernel(("center_sq_dists",)) == (pytest.approx(100e-9), 1)
    # gaps: 200-300 (run), 400-900 (publish at its midpoint 650)
    assert red.top_gaps() == [["bench.publish", pytest.approx(500e-9)],
                              ["bench.federation_run",
                               pytest.approx(100e-9)]]
    assert red.top_ops(1) == [["fusion.2", pytest.approx(100e-9)]]
    assert red.spans["bench.publish"] == pytest.approx(500e-9)


def test_kernel_per_run_counts_runs_and_refuses_a_window_without_them():
    ops = [ev("center_sq_dists.1", 100, 200), ev("fused_coalition_stats.1",
                                                 200, 400),
           ev("center_sq_dists.1", 500, 600), ev("fused_coalition_stats.1",
                                                 600, 800)]
    red = trace.reduce_trace(fake(ops, [ev("bench.window", 0, 1000)]))
    names = ("center_sq_dists", "fused_coalition_stats")
    assert red.kernel_per_run(names) == (pytest.approx(600e-9), 2.0)
    with pytest.raises(trace.MissingEvents, match="fused_coalition"):
        red.kernel_per_run(("fused_coalition_v2",))


def test_a_trace_without_the_window_span_or_device_ops_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_trace(fake([ev("a", 0, 1)], []))
    with pytest.raises(ValueError, match="device operation"):
        trace.reduce_trace(fake([], [ev("bench.window", 0, 10)]))


def test_recorded_chip_trace():
    from jax.profiler import ProfileData

    red = trace.reduce_trace(ProfileData.from_file(RECORDED))
    assert red.chips == 1
    assert 0 < red.busy_s < red.window_s
    secs, events = red.kernel(("center_sq_dists", "fused_coalition_stats"))
    # three rounds of two kernels ran inside the window span on the host;
    # the device clock reads about 1 ms earlier, so the first round's
    # kernels fall before the span's start and are clipped away
    assert events == 4 and secs > 0
    assert red.op_counts["center_sq_dists.1"] == 2
    assert {n for n, _ in red.top_gaps()} <= {
        "bench.federation_run", "bench.inputs", "outside bench spans"}
