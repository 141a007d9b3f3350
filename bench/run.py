#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload paper_cnn_n10.train --seed 7 \\
        --seconds 30 --trace 0

Loads, warms up, measures for ``--seconds`` and prints, as the last line of
standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``
of the traced window, and last ``checks``: each number compared with the
plain reference beside its limit.  The checks are also the last lines of
standard error.

Exits non-zero, and prints no result, when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from ``bench/peaks.json``,
and when anything compiles inside the measured window.  The cell's files
are found by name (see ``bench/harness/registry.py``); JAX's compilation
cache is kept under ``.bench_cache/`` and the run's scratch files under
``.bench_out/``, both in the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness import registry  # noqa: E402


class RunError(RuntimeError):
    """The run cannot give a valid result."""


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell, ctx: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        value = registry.metric_reader(m["name"], BENCH)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(cell, res: dict) -> dict:
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in res:
            raise RunError(f"the cell's module measured no {m['name']}")
        out[m["name"]] = {"value": res[m["name"]], "unit": m["unit"]}
    return out


def execute(cell, args, devices, stats, t_start: float) -> dict:
    """Run the cell's driver and assemble the result line."""
    from harness import compare, device, trace

    info = device.require(devices, cell.chips)
    peaks = device.peaks_for(info["kind"])
    work = os.path.join(ROOT, ".bench_out", cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    trace_dir = os.path.join(work, "trace") if args.trace else None
    kind = cell.traffic["kind"]
    try:
        driver = importlib.import_module(f"harness.{kind}")
    except ModuleNotFoundError:
        raise RunError(f"no driver bench/harness/{kind}.py for the traffic "
                       f"kind {kind!r}") from None
    res = driver.run(cell, args.seed, args.seconds, trace_dir, work,
                     t_start, stats)
    if res["window_compiles"]["compiles"] or (
            res["window_compiles"]["cache_hits"]
            + res["window_compiles"]["cache_misses"]):
        raise RunError(f"compiled inside the window: {res['window_compiles']}")
    checks = compare.judge(res["numbers"], cell.limits)
    info["memory_peak_bytes"] = res["memory_peak_bytes"]
    out = {"correct": compare.passed(checks) and not res["failed"],
           "attempted": res["attempted"], "failed": res["failed"]}
    print(f"bench: set-up {res['setup_s']:.3f} s, window "
          f"{res['window_s']:.3f} s, reference {res['reference_s']:.3f} s",
          file=sys.stderr)
    print(f"bench: numbers {json.dumps(plain(res['numbers']))}",
          file=sys.stderr)
    if args.trace:
        t0 = time.perf_counter()
        red = trace.load(trace_dir)
        print(f"bench: trace reduced in {time.perf_counter() - t0:.3f} s",
              file=sys.stderr)
        ctx = dict(res, kind=kind, config=cell.config, peaks=peaks,
                   trace=red)
        out["metrics"] = per_layer(cell, ctx)
        info["busy_s"] = red.busy_s
        info["window_s"] = red.window_s
        out["device"] = info
        out["breakdown"] = {"device_ops": red.top_ops(),
                            "idle_gaps": red.top_gaps()}
    else:
        out["metrics"] = end_to_end(cell, res)
        out["device"] = info
    out["checks"] = checks
    return out


def plain(v):
    """JSON-ready copy: numpy scalars to Python, non-finite floats to None."""
    if isinstance(v, dict):
        return {k: plain(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [plain(x) for x in v]
    if hasattr(v, "item"):
        v = v.item()
    if isinstance(v, float) and not math.isfinite(v):
        return None
    return v


def main(argv=None) -> int:
    args = parse(argv)
    cell = registry.load_cell(ROOT, args.workload)
    import jax

    from harness import compile_stats, device, trace

    device.enable_compile_cache(ROOT)
    stats = compile_stats.CompileStats()
    try:
        out = execute(cell, args, jax.devices(), stats, T_START)
    except (device.DeviceError, RunError, trace.MissingEvents) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(plain(out), allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
