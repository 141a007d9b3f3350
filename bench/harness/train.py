"""Training cells: federation rounds through ``Federation.run``.

Set-up builds the inputs from the seed (synthetic digits split into the
configuration's shards, the CNN's weights in one jitted call), then one
:class:`repro.core.server.Federation` as ``repro.launch.train.run_fl``
builds it, with a :class:`repro.serve.ModelStore` that receives a snapshot
every ``snapshot_every`` rounds.  A run of that object is one federation of
``1 + snapshot_every * chunks`` rounds: the round-0 prologue, then
``chunks`` scanned chunks of ``snapshot_every`` rounds, each ending in a
published snapshot.  ``chunks`` is the fewest that fill ``--seconds`` at the
configuration's ``round_s`` (:func:`chunks`).

Its first call, with run index 0, is the warm-up: it runs every program the
timed call runs, at the same shapes, and it is what the reference checks
(round 0, and the scanned rounds up to the first chunk's end).  The window
is the second call, with run index 1, timed from its start to its return:
one whole federation, as an operator runs it, start-up and hand-over at
chunk boundaries included.

The traffic mix (``bench/traffic/<name>.json``, ``kind: "train"``) holds
``trace_seconds``, the ``--seconds`` of a ``--trace 1`` run, whose window is
sized the same way.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, device, digits, reference, trace

SPAN_RUN = "bench.federation_run"
SPAN_INPUTS = "bench.inputs"


def seeds(seed: int) -> dict[str, int]:
    """Independent 31-bit seeds for data, weights and runs from any
    ``--seed`` (SeedSequence takes integers of any size)."""
    data, init, run, sample = np.random.SeedSequence(
        seed % 2**64).generate_state(4)
    return {"data": int(data) >> 1, "init": int(init) >> 1,
            "run": int(run) >> 1, "sample": int(sample) >> 1}


#: the test set's seed: one fixed held-out set, as MNIST's test set is.  The
#: in-round eval closes over it, so it is a constant of the round programs;
#: a set drawn from ``--seed`` would make them new programs in every run,
#: compiled afresh and never found in the persistent cache.
TEST_SEED = 10_000


def make_data(cfg: dict, seed: int) -> tuple[dict, dict]:
    """Train shards ``{"x": (S, n, 28, 28, 1), "y": (S, n)}`` and the test
    set, host arrays: ``n_train`` digits from ``seed`` dealt at random (the
    ``iid`` split) into S shards of ``examples_per_client``, and
    ``n_test`` digits from :data:`TEST_SEED`."""
    x, y = digits.digits(cfg["n_train"], seed=seed)
    xt, yt = digits.digits(cfg["n_test"], seed=TEST_SEED)
    s, n = cfg["shards"], cfg["examples_per_client"]
    idx = np.random.default_rng(seed).permutation(cfg["n_train"])[:s * n]
    idx = idx.reshape(s, n)
    return {"x": x[idx], "y": y[idx]}, {"x": xt, "y": yt}


def inputs(cfg: dict, seed: int):
    """Device shards, host test set, θ's start and the base run key; the
    weights are made on the device in one jitted call."""
    sd = seeds(seed)
    host, test = make_data(cfg, sd["data"])
    data = jax.tree.map(jnp.asarray, host)
    params0 = jax.jit(lambda k: reference.init_cnn(k, cfg["model"]))(
        jax.random.key(sd["init"]))
    return data, test, params0, jax.random.key(sd["run"])


def chunks(cfg: dict, seconds: float) -> int:
    """Scanned chunks in one timed run: the fewest whose rounds, at the
    configuration's ``round_s`` seconds a round, fill ``seconds`` (one at
    the least)."""
    return max(1, math.ceil(seconds / (cfg["snapshot_every"] * cfg["round_s"])))


def build(cfg: dict, n_chunks: int, data: dict, test: dict, store_dir: str):
    """The timed object: a Federation built as ``run_fl`` builds it."""
    from repro import sim
    from repro.core import strategies
    from repro.core.client import ClientConfig
    from repro.core.server import Federation, FederationConfig
    from repro.models import zoo
    from repro.serve import ModelStore

    model = zoo.make_model(cfg["model"]["name"])
    c, k = cfg["n_clients"], cfg["n_coalitions"]
    rounds = 1 + cfg["snapshot_every"] * n_chunks
    strategy = strategies.make_strategy(cfg["method"], n_clients=c,
                                        n_coalitions=k,
                                        backend=cfg["backend"])
    fcfg = FederationConfig(
        n_clients=c, n_coalitions=k, rounds=rounds, method=cfg["method"],
        client=ClientConfig(epochs=cfg["local_epochs"],
                            batch_size=cfg["batch_size"], lr=cfg["lr"]),
        backend=cfg["backend"], engine=cfg["engine"],
        fleet_size=cfg.get("fleet_size"), sim=sim.SimConfig())
    xte, yte = jnp.asarray(test["x"]), jnp.asarray(test["y"])
    fed = Federation(model.loss_fn, lambda p: model.accuracy(p, xte, yte),
                     fcfg, strategy=strategy)
    shutil.rmtree(store_dir, ignore_errors=True)
    store = ModelStore(store_dir)
    return fed, store, rounds


def snapshot_view(store, rounds: tuple[int, ...]) -> dict:
    """θ and the clients' mean weights of the published ``rounds``."""
    snaps = {r: store.load(r) for r in rounds}
    return {"theta": {r: jax.tree.map(np.asarray, s.global_params)
                      for r, s in snaps.items()},
            "wmean": {r: compare.mean_of_members(
                s.barycenters, s.counts, s.global_params)
                for r, s in snaps.items()}}


def run(cell, seed: int, seconds: float, trace_dir: str | None,
        work_dir: str, t_start: float, stats) -> dict:
    cfg = cell.config
    if trace_dir is not None:
        seconds = min(seconds, cell.traffic["trace_seconds"])
    with jax.profiler.TraceAnnotation(SPAN_INPUTS):
        data, test, params0, base_key = inputs(cfg, seed)
    fed, store, rounds = build(cfg, chunks(cfg, seconds), data, test,
                               os.path.join(work_dir, "store"))

    def one_run(i: int):
        with jax.profiler.TraceAnnotation(SPAN_RUN):
            return fed.run(params0, data, jax.random.fold_in(base_key, i),
                           snapshot_every=cfg["snapshot_every"], store=store)

    _, hist = one_run(0)                       # warm-up and checked run
    end = cfg["snapshot_every"]                # the first chunk's last round
    prog = dict(snapshot_view(store, (0, end)), loss=hist.train_loss,
                assignment=hist.assignments)
    setup_mark = stats.mark()
    setup_compile = stats.seconds
    setup_s = time.perf_counter() - t_start

    with trace.capture(trace_dir):
        t0 = time.perf_counter()
        _, h = one_run(1)
        window = time.perf_counter() - t0
    in_window = stats.since(setup_mark)
    failed = int(np.sum(~np.isfinite(np.asarray(h.trace.loss))))
    memory = device.memory_peak_bytes(jax.devices())

    del fed, store
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.follow(params0, data, jax.random.fold_in(base_key, 0),
                           cfg, end + 1, keep=(0, end))
    start = jax.tree.map(np.asarray, params0)
    numbers = compare.train_numbers(prog, ref, start, end)
    return {"setup_s": setup_s, "window_s": window, "rounds": rounds,
            "reference_s": time.perf_counter() - t_ref,
            "failed": failed, "attempted": rounds,
            "rounds_per_s": rounds / window, "numbers": numbers,
            "reference": ref, "start": start, "memory_peak_bytes": memory,
            "window_compiles": in_window, "setup_compile_s": setup_compile}
