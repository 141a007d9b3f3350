#!/usr/bin/env python3
"""Readings that a training cell's limits are set from.

    python3 bench/control.py --workload paper_cnn_n10.train --seeds 1,2,3

For each seed, in one process: the program's numbers against the float32
reference, as a run computes them (the lower readings, from a run of one
chunk: the rounds compared are the same at any run length); the bfloat16
reference put in the program's place (the control); and two planted faults
in the reference put in the program's place: half of every batch left out,
and θ left unchanged by every scanned round.  A local step that returns its
state unchanged reads 1 on ``update0`` by definition and needs no run.  One
JSON line per seed and reading goes to standard output.  The benchmark's
own runs never run this.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness import registry  # noqa: E402

#: the reference put in the program's place: the control, then the faults
OTHERS = (("control_bf16", "dtype"), ("fault_half_batch", "half_batch"),
          ("fault_frozen_theta", "frozen_theta"))


def readings(cell, seed: int, stats, work: str | None = None) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import compare, reference, train

    t0 = time.perf_counter()
    work = work or os.path.join(ROOT, ".bench_out", "control")
    res = train.run(cell, seed, 0.0, None, work, time.perf_counter(), stats)
    ref = res["reference"]
    m0 = ref["margin"][0]
    out = [{"seed": seed, "reading": "program",
            "seconds": time.perf_counter() - t0,
            "reference_s": res["reference_s"], **res["numbers"],
            "margin0": sorted(float(m) for m in m0[np.isfinite(m0)]),
            "medoid_margin": [float(m) for m in ref["medoid_margin"]]}]
    cfg = cell.config
    end = cfg["snapshot_every"]
    data, _, params0, base_key = train.inputs(cfg, seed)
    key = jax.random.fold_in(base_key, 0)
    for name, option in OTHERS:
        kw = {option: jnp.bfloat16 if option == "dtype" else True}
        t0 = time.perf_counter()
        other = reference.follow(params0, data, key, cfg, end + 1,
                                 keep=(0, end), **kw)
        out.append({"seed": seed, "reading": name,
                    "seconds": time.perf_counter() - t0,
                    **compare.train_numbers(other, ref, res["start"], end)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell = registry.load_cell(ROOT, args.workload)
    from harness import compile_stats, device

    import jax

    device.require(jax.devices(), cell.chips)
    device.enable_compile_cache(ROOT)
    stats = compile_stats.CompileStats()
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in readings(cell, seed, stats):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
