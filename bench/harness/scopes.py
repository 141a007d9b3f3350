"""Read the program's own scopes and host spans from a traced window.

The program names the phases of a round on the device with
``jax.named_scope`` (``fl.local_phase``, ``fl.w_build``,
``fl.coalition_round``, ``fl.eval``) and its host work between device
programs with ``jax.profiler.TraceAnnotation`` spans (``fl.run``,
``fl.dispatch``, ``fl.publish``, ...).  :func:`reduce_scopes` reads both
from the same ``.xplane.pb`` that :mod:`harness.trace` reduces, over the
same window (the ``bench.window`` span), and returns a :class:`Scopes`:

* ``scope_seconds``: device seconds of the ops under each ``fl.*`` scope,
  per chip.  An op is under a scope when a ``/``-separated component of
  its JAX name stack (``tf_op``, read by :mod:`harness.xplane_meta`) is the
  scope, inside JAX's transform wrappers or not
  (``transpose(jvp(fl.local_phase))``).  An op that XLA added with no name
  stack of its own takes the one its consumers share in the program's HLO
  (:func:`harness.xplane_meta.consumer_names`): the in-place writes that
  W's concatenate becomes and the layout copies feeding it count under
  ``fl.w_build``.  Control-flow ops (``while``, ``conditional``,
  ``call``), whose events span the ops inside them, are left out, as in
  :meth:`harness.trace.Reduced.top_ops`;
* ``idle_by_span``: device-idle seconds per chip under each innermost host
  span, each gap split over the spans by overlap;
* ``span_counts``: how many times each host span started in the window.

A program without the scopes and spans (``fl.run`` absent, no op scoped)
reads as :attr:`Scopes.instrumented` false, and the metrics that read it
report nothing: the program before the scopes runs under this benchmark.
One with them that lacks a scope a metric reads fails the run
(:class:`harness.trace.MissingEvents`).  A program that renamed every
scope and ``fl.run`` would read as one without them; the names the readers
read are checked against the program's own in tier 1
(``tests/bench/test_bench_scopes.py``).
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass

from harness import trace, xplane_meta

SCOPE_PREFIX = "fl."
SPAN_PREFIXES = (trace.SPAN_PREFIX, SCOPE_PREFIX)
RUN_SPAN = "fl.run"
OUTSIDE = "outside spans"


@dataclass
class Scopes:
    window_s: float
    chips: int
    op_s: float                         # per chip, control flow left out
    scope_seconds: dict[str, float]     # per chip
    scoped_s: float                     # per chip, under any fl.* scope
    idle_by_span: dict[str, float]      # per chip
    span_counts: dict[str, int]

    @property
    def instrumented(self) -> bool:
        return bool(self.scope_seconds) or RUN_SPAN in self.span_counts

    def scope(self, name: str) -> float | None:
        """Device seconds per chip under the scope ``name``; None for a
        program without scopes, :class:`MissingEvents` for one that has
        them but ran no op under ``name`` in the window."""
        if not self.instrumented:
            return None
        secs = self.scope_seconds.get(name, 0.0)
        if secs <= 0:
            raise trace.MissingEvents(f"the traced window holds no op under "
                                      f"the program scope {name!r}")
        return secs

    def idle(self, names: tuple[str, ...]) -> float | None:
        """Device-idle seconds per chip under the host spans ``names``;
        None for a program without spans."""
        if not self.instrumented:
            return None
        return sum(self.idle_by_span.get(n, 0.0) for n in names)


def components(tf_op: str) -> set[str]:
    """The components of a name stack, each with JAX's transform wrappers
    taken off: ``jit(f)/transpose(jvp(fl.x))/mul`` -> ``{f, fl.x, mul}``."""
    out = set()
    for part in tf_op.split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        out.add(part)
    return out


def _segments(spans, lo: int, hi: int) -> list[tuple[int, int, str]]:
    """``[lo, hi]`` cut where any span starts or ends, each piece named
    after the shortest span that holds it."""
    cuts = sorted({lo, hi, *(t for s, e, _ in spans for t in (s, e)
                             if lo < t < hi)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        best, best_len = OUTSIDE, None
        for s, e, n in spans:
            if s <= a and b <= e and (best_len is None or e - s < best_len):
                best, best_len = n, e - s
        out.append((a, b, best))
    return out


def _split(gaps, segments, into: dict[str, float]) -> None:
    """Add each gap's overlap with each segment to its segment's name."""
    starts = [a for a, _, _ in segments]
    for g0, g1 in gaps:
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(segments) and segments[i][0] < g1:
            a, b, name = segments[i]
            over = min(b, g1) - max(a, g0)
            if over > 0:
                into[name] = into.get(name, 0.0) + over * 1e-9
            i += 1


def reduce_scopes(pdata, op_scopes: dict[str, dict[str, str]]) -> Scopes:
    """Reduce a ``jax.profiler.ProfileData`` with the ops' name stacks
    ``{program id: {op name: tf_op}}`` (see the module docstring)."""
    spans: list[tuple[int, int, str]] = []
    devices = []
    for plane in pdata.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            ops = next((lines[n] for n in trace.OPS_LINES if n in lines),
                       None)
            if ops is None:
                continue
            modules = sorted(
                (ev.start_ns, ev.end_ns, xplane_meta.program_of(ev.name))
                for n in trace.MODULE_LINES if n in lines
                for ev in lines[n].events)
            devices.append((modules, [(ev.start_ns, ev.end_ns,
                                       trace.op_name(ev.name))
                                      for ev in ops.events]))
            continue
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [(s, e) for s, e, n in spans if n == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {trace.WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError("the trace holds no device operation")
    lo, hi = windows[0]
    inner = [(max(s, lo), min(e, hi), n) for s, e, n in spans
             if n != trace.WINDOW_SPAN and e > lo and s < hi]
    segments = _segments(inner, lo, hi)
    span_counts: dict[str, int] = {}
    for s, _, n in spans:
        if n != trace.WINDOW_SPAN and lo <= s < hi:
            span_counts[n] = span_counts.get(n, 0) + 1
    memo: dict[tuple[str, str], tuple[str, ...]] = {}
    scope_seconds: dict[str, float] = {}
    idle: dict[str, float] = {}
    op_s = scoped_s = 0.0
    for modules, events in devices:
        starts = [s for s, _, _ in modules]
        in_win = [(s, e, n) for s, e, n in events if e > lo and s < hi]
        for s, e, n in in_win:
            if n.startswith(trace.CONTAINERS):
                continue
            secs = (min(e, hi) - max(s, lo)) * 1e-9
            op_s += secs
            i = bisect.bisect_right(starts, s) - 1
            program = modules[i][2] if i >= 0 and s < modules[i][1] else ""
            key = (program, n)
            if key not in memo:
                tf_op = op_scopes.get(program, {}).get(n, "")
                memo[key] = tuple(sorted(
                    c for c in components(tf_op)
                    if c.startswith(SCOPE_PREFIX)))
            for c in memo[key]:
                scope_seconds[c] = scope_seconds.get(c, 0.0) + secs
            if memo[key]:
                scoped_s += secs
        busy = trace._union(trace._clip([(s, e) for s, e, _ in in_win],
                                        lo, hi))
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        _split([(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a],
               segments, idle)
    chips = len(devices)
    return Scopes(window_s=(hi - lo) * 1e-9, chips=chips, op_s=op_s / chips,
                  scope_seconds={k: v / chips
                                 for k, v in scope_seconds.items()},
                  scoped_s=scoped_s / chips,
                  idle_by_span={k: v / chips for k, v in idle.items()},
                  span_counts=span_counts)


def load(path: str) -> Scopes:
    from jax.profiler import ProfileData

    return reduce_scopes(ProfileData.from_file(path),
                         xplane_meta.op_scopes(path))


def newest_trace(root: str) -> str:
    """The ``.xplane.pb`` that ``bench/run.py`` wrote last in the checkout
    at ``root`` (each run writes under ``.bench_out/<cell>/trace``)."""
    paths = glob.glob(os.path.join(root, ".bench_out", "*", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no traced run under {root}/.bench_out")
    return max(paths, key=os.path.getmtime)


def of_run(ctx: dict) -> Scopes:
    """The :class:`Scopes` of the traced run whose result ``ctx`` the
    per-layer readers are given, reduced once and kept in ``ctx``.  The
    run's trace is the newest under the checkout; its window must be the
    one :mod:`harness.trace` reduced for ``ctx``."""
    if "scopes" not in ctx:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        red = load(newest_trace(root))
        if red.window_s != ctx["trace"].window_s:
            raise trace.MissingEvents(
                f"the newest trace's window ({red.window_s} s) is not the "
                f"run's ({ctx['trace'].window_s} s)")
        ctx["scopes"] = red
    return ctx["scopes"]
