"""Reduce a JAX profiler trace to device busy time, kernel time and gaps.

The benchmark traces one steady window per ``--trace 1`` run, with its own
host spans (``jax.profiler.TraceAnnotation``, names starting ``bench.``)
around each call into the program.  :func:`reduce_trace` reads the
``.xplane.pb`` that ``jax.profiler`` wrote, through
``jax.profiler.ProfileData`` and nothing else, and returns a
:class:`Reduced`:

* busy: the union of the intervals in which an operation ran on each chip,
  clipped to the window, averaged over chips;
* per-name device time, for the top operations and for kernel sums;
* idle gaps: the stretches between busy intervals, each named after the
  innermost benchmark span the host was in at the gap's midpoint.
"""
from __future__ import annotations

import contextlib
import glob
import os
from dataclasses import dataclass, field

#: the host span that brackets the traced window
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
#: the device line whose events are single operations (TPU traces); the
#: module line holds whole programs and is the fallback
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
#: ops whose events enclose other ops' events
CONTAINERS = ("while", "conditional", "call")
#: how many of the longest idle gaps are named and kept
MAX_GAPS = 10


class MissingEvents(RuntimeError):
    """A traced window lacks the events that a metric of its cell reads."""


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # averaged over chips
    chips: int
    op_seconds: dict[str, float]        # summed over chips
    op_counts: dict[str, int]
    gaps: list[tuple[str, float]]       # the longest, longest first
    spans: dict[str, float] = field(default_factory=dict)

    def kernel(self, prefixes: tuple[str, ...]) -> tuple[float, int]:
        """Summed device seconds and event count of ops whose name starts
        with any of ``prefixes`` (all chips)."""
        secs = sum(s for n, s in self.op_seconds.items()
                   if n.startswith(prefixes))
        count = sum(c for n, c in self.op_counts.items()
                    if n.startswith(prefixes))
        return secs, count

    def kernel_per_run(self, names: tuple[str, ...]) -> tuple[float, float]:
        """Summed device seconds of the kernels ``names`` and how many times
        the set of them ran (events over ``len(names)``); a window in which
        none ran raises :class:`MissingEvents`, since a cell only lists a
        kernel's metrics where its path runs that kernel."""
        secs, events = self.kernel(names)
        if events == 0 or secs <= 0:
            raise MissingEvents(f"the traced window holds no event of the "
                                f"kernels {list(names)}")
        return secs, events / len(names)

    def top_ops(self, n: int = 10) -> list[list]:
        """The ops with the most device time; control flow (``while``,
        ``conditional``, ``call``), whose events span the ops inside them,
        is left out."""
        ranked = sorted(((k, v) for k, v in self.op_seconds.items()
                         if not k.startswith(CONTAINERS)),
                        key=lambda kv: -kv[1])
        return [[name, secs] for name, secs in ranked[:n]]

    def top_gaps(self, n: int = 10) -> list[list]:
        return [[name, secs] for name, secs in self.gaps[:n]]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(event_name: str) -> str:
    """The HLO instruction's name: TPU traces name an op event by its whole
    HLO text, ``%fused_coalition_stats.1 = (f32[...]) custom-call(...)``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _clip_named(spans, lo: int, hi: int):
    return [(max(s, lo), min(e, hi), n) for s, e, n in spans
            if e > lo and s < hi]


def _innermost(spans: list[tuple[int, int, str]], t: int) -> str:
    """Name of the shortest benchmark span containing ``t``."""
    best, best_len = "outside bench spans", None
    for s, e, name in spans:
        if s <= t <= e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def _device_lines(plane):
    lines = {ln.name: ln for ln in plane.lines}
    for names in (OPS_LINES, MODULE_LINES):
        for n in names:
            if n in lines:
                return lines[n]
    return None


def reduce_trace(pdata) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` (see the module docstring)."""
    spans: list[tuple[int, int, str]] = []
    devices = []
    for plane in pdata.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            line = _device_lines(plane)
            if line is not None:
                devices.append([(ev.start_ns, ev.end_ns, op_name(ev.name))
                                for ev in line.events])
            continue
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    if not devices or not any(devices):
        raise ValueError("the trace holds no device operation")
    op_seconds: dict[str, float] = {}
    op_counts: dict[str, int] = {}
    busy_total = 0.0
    raw_gaps: list[tuple[int, int]] = []
    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    for events in devices:
        in_win = [(s, e, n) for s, e, n in events if e > lo and s < hi]
        for s, e, n in in_win:
            op_seconds[n] = op_seconds.get(n, 0.0) + (min(e, hi)
                                                      - max(s, lo)) * 1e-9
            op_counts[n] = op_counts.get(n, 0) + 1
        busy = _union(_clip([(s, e) for s, e, _ in in_win], lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        raw_gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                     if b > a]
    raw_gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [(_innermost(inner, (a + b) // 2), (b - a) * 1e-9)
            for a, b in raw_gaps[:MAX_GAPS]]
    span_secs: dict[str, float] = {}
    for s, e, n in _clip_named(inner, lo, hi):
        span_secs[n] = span_secs.get(n, 0.0) + (e - s) * 1e-9
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total / len(devices), chips=len(devices),
                   op_seconds=op_seconds, op_counts=op_counts, gaps=gaps,
                   spans=span_secs)


def load(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_trace(ProfileData.from_file(find_xplane(trace_dir)))



@contextlib.contextmanager
def capture(trace_dir: str | None):
    """Profile the enclosed window into ``trace_dir`` under the
    :data:`WINDOW_SPAN` host span; a no-op when ``trace_dir`` is None."""
    if trace_dir is None:
        yield
        return
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # no event per Python call: it slows
    #                                     the host the window measures
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()
