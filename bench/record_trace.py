#!/usr/bin/env python3
"""Record a small traced federation on one TPU, for the reduction's tests.

    python3 bench/record_trace.py --out .bench_out/small_fl_trace

The paper's CNN at its widths (D = 582,026), 10 clients of 20 digits each
(2 local SGD steps a round), 3 coalitions, the pallas backend: a run of 3
rounds, round 0 and two scanned chunks of one round, each ending in a
published snapshot, a checkpoint and ledger records, so that every ``fl.*``
host span of ``core/server.py`` fires.  A first run compiles; the second is
traced by :func:`harness.trace.capture` inside the ``bench.federation_run``
span, as a ``--trace 1`` run traces its window.  The ``/host:metadata``
plane (the programs' HLO, about 1.3 MB here) is cut to the fields that
:mod:`harness.xplane_meta` reads in the ``.xplane.pb`` written.  Prints
its path and size (``tests/bench/data/small_fl_trace.xplane.pb`` is one).
"""
import argparse
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness import registry, xplane_meta  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax

    from harness import device, trace, train
    from repro.obs import InMemorySink

    device.require(jax.devices(), 1)
    cfg = registry.load_cell(ROOT, "paper_cnn_n10.train").config
    cfg.update(examples_per_client=20, n_train=200, n_test=100,
               snapshot_every=1)
    work = os.path.join(ROOT, ".bench_out", "record_trace")
    shutil.rmtree(work, ignore_errors=True)
    shutil.rmtree(args.out, ignore_errors=True)
    data, test, params0, key = train.inputs(cfg, args.seed)
    fed, store, _ = train.build(cfg, 2, data, test,
                                os.path.join(work, "store"))

    def one_run(i: int):
        with jax.profiler.TraceAnnotation(train.SPAN_RUN):
            return fed.run(params0, data, jax.random.fold_in(key, i),
                           snapshot_every=1, store=store, ckpt_every=1,
                           ckpt_dir=os.path.join(work, f"ckpt{i}"),
                           sink=InMemorySink())

    one_run(0)
    with trace.capture(args.out):
        one_run(1)
    path = trace.find_xplane(args.out)
    with open(path, "rb") as f:
        kept = xplane_meta.with_plane_pruned(
            f.read(), xplane_meta.METADATA_PLANE,
            xplane_meta.METADATA_FIELDS)
    with open(path, "wb") as f:
        f.write(kept)
    print(f"{path} {os.path.getsize(path)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
