"""Put the benchmark's own directory on the import path of its tests."""
import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


import pytest  # noqa: E402

ROOT = os.path.dirname(BENCH)


@pytest.fixture
def tiny_cell():
    """A cell of BENCHMARK.json with its configuration cut to a size the CPU
    runs in seconds (a chunk of 2 rounds, one chunk a run), its limits the
    cell's own."""
    from harness import registry

    def make(workload: str):
        cell = registry.load_cell(ROOT, workload)
        cfg = cell.config
        shards = 4 if cfg["fleet_size"] is None else 30
        cfg.update(n_clients=4 if cfg["fleet_size"] is None else 8,
                   shards=shards, examples_per_client=20,
                   n_train=shards * 20, n_test=50, snapshot_every=2,
                   round_s=60.0)
        return cell

    return make
