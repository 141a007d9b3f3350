#!/usr/bin/env python3
"""Chip smoke test: the coalition FL round, and serving from it, on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh-parallel round, four chips

One chip.  Trains the paper's CNN (D = 582,026) federation, 10 clients in 3
coalitions, through ``repro.launch.train.run_fl`` with the Pallas kernels,
and checks that accuracy and loss are finite and learning happens on the
committed synthetic digits.  It checks that the Pallas round compiles to
native TPU kernels (``tpu_custom_call``) and matches the XLA reference on
one W.  Then the XLA streaming reference, from the same seed, replays every
round of the Pallas run from that run's checkpoint, and both must elect the
same assignment and medoids, with θ within :data:`THETA_RTOL`.  Last, it
serves routed batches from the Pallas run's snapshot store through a hot
swap with one compilation.

Four chips.  Runs only the coalition round: as a ``Federation`` with
``mesh="data=4"`` runs it, on the ``pallas`` and ``xla`` backends, against
the same round dense on one chip, on one W.  It reports what each device
holds after each.

Progress goes to earlier lines.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failed check raises, so the script exits non-zero and prints no result
line; so it does when JAX finds no TPU.  It runs in one process, keeps JAX's
compilation cache where :mod:`repro.launch.compile_cache` says, and writes
its stores and checkpoints under ``chip_smoke_out/`` (git-ignored, wiped at
the start of every run).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import checkpoint  # noqa: E402
from repro.core import fused as fz  # noqa: E402
from repro.core import pytree  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch import serve as serve_cli  # noqa: E402
from repro.launch import train as train_cli  # noqa: E402
from repro.models.cnn import CNNConfig  # noqa: E402
from repro.serve import BatchServer, ModelStore  # noqa: E402

OUT = os.path.join(ROOT, "chip_smoke_out")

# The paper's widths (§IV.D): N = 10 clients, K = 3 coalitions, batch 10,
# the MNIST CNN.  Depth is cut: few rounds, one local epoch, a small train
# split (enough for accuracy to clear chance by a wide margin).
CLIENTS, COALITIONS, BATCH = 10, 3, 10
ROUNDS, LOCAL_EPOCHS, N_TRAIN, N_TEST = 4, 1, 3000, 1000
#: last-round test accuracy must clear this (chance is 0.1)
ACC_MIN = 0.3
#: θ agreement of one round computed two ways from the same inputs:
#: max |Δθ| <= THETA_RTOL · max |θ|.  The ways differ in f32 summation order
#: (chunk widths, centered Gram form vs diff form, mesh shards).
THETA_RTOL = 1e-5
#: the same round's medoid distances, pallas vs xla: max |Δd²| / max d²
MED_RTOL = 1e-4
#: a served answer vs a direct forward through the routed model:
#: max |Δ logits| <= SERVE_RTOL · max |logits|
SERVE_RTOL = 1e-4
SERVE_BATCH, SERVE_REPEATS = 64, 4


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileStats:
    """Backend compile seconds and persistent-cache hits and misses, summed
    from ``jax.monitoring`` events over the life of the process."""

    def __init__(self):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.hits, self.misses

    def since(self, snap) -> str:
        s, h, m = snap
        return (f"compile {self.seconds - s:.3f} s, cache hits {self.hits - h}"
                f" misses {self.misses - m}")


def device_phase(chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    print(f"[device] {devs}")
    print(f"[device] platform {d0.platform}, kind {d0.device_kind}, "
          f"count {len(devs)}")
    if require_tpu:
        check(d0.platform == "tpu",
              f"no TPU: JAX found platform {d0.platform!r}")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, "
          f"JAX found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def _round_seconds(store_dir: str) -> list[float]:
    """Host seconds between consecutive snapshot publishes: each is one
    round's chunk program plus its checkpoint and publish I/O (round 1 also
    compiles the one-round chunk program)."""
    times = [os.path.getmtime(os.path.join(store_dir, f"step_{r:08d}",
                                           "meta.json"))
             for r in ModelStore(store_dir).rounds()]
    return [round(b - a, 3) for a, b in zip(times, times[1:])]


def train_phase(stats: CompileStats, backend: str, tag: str, *,
                rounds: int = ROUNDS, resume: tuple | None = None) -> dict:
    """One federation run through the training CLI's own ``run_fl``.

    ``resume=(ckpt_dir, step)`` starts from a copy of that checkpoint, so
    the run computes rounds ``step + 1 ..`` from another run's carry.
    """
    run_dir = os.path.join(OUT, tag)
    ckpt_dir = os.path.join(run_dir, "ckpt")
    argv = ["--mode", "fl", "--model", "cnn", "--method", "coalition",
            "--engine", "scan", "--backend", backend,
            "--clients", str(CLIENTS), "--coalitions", str(COALITIONS),
            "--batch-size", str(BATCH), "--rounds", str(rounds),
            "--local-epochs", str(LOCAL_EPOCHS), "--n-train", str(N_TRAIN),
            "--n-test", str(N_TEST), "--seed", "0",
            "--snapshot-dir", os.path.join(run_dir, "store"),
            "--snapshot-every", "1", "--ckpt-dir", ckpt_dir,
            "--ckpt-every", "1"]
    if resume is not None:
        src, step = resume
        shutil.copytree(os.path.join(src, f"step_{step:08d}"),
                        os.path.join(ckpt_dir, f"step_{step:08d}"))
        argv += ["--resume"]
    args = train_cli.build_parser().parse_args(argv)
    snap = stats.snapshot()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(sys.stderr):     # its JSON summary
        out = train_cli.run_fl(args)
    wall = time.perf_counter() - t0

    acc, loss = out["test_acc"], out["train_loss"]
    check(out["source"] == "synthetic-digits",
          f"[{tag}] trained on {out['source']!r}, not the committed "
          "synthetic digits")
    check(len(acc) == rounds and len(loss) == rounds,
          f"[{tag}] expected {rounds} rounds, got {len(acc)}")
    check(all(np.isfinite(acc)) and all(np.isfinite(loss)),
          f"[{tag}] non-finite accuracy or loss: {acc} {loss}")
    first = 0 if resume is None else resume[1] + 1
    check(out["published_rounds"] == list(range(first, rounds)),
          f"[{tag}] published rounds {out['published_rounds']}")
    print(f"[train {tag}] wall {wall:.3f} s, {stats.since(snap)}, "
          f"round seconds {_round_seconds(args.snapshot_dir)}, "
          f"test_acc {acc[first:]}, train_loss {loss[first:]}")
    return {"tag": tag, "store": args.snapshot_dir, "ckpt": ckpt_dir,
            "acc": acc}


def kernel_phase(stats: CompileStats, run: dict, native: bool) -> None:
    """The Pallas fused round at the paper's D: native TPU kernels on the
    chip (``tpu_custom_call``, not interpret mode), and the same round as
    the XLA reference on one W around the run's last θ."""
    d = CNNConfig().n_params()
    snap = stats.snapshot()
    lowered = jax.jit(lambda w_, c_: fz.fused_round(w_, c_, backend="pallas")
                      ).lower(jax.ShapeDtypeStruct((CLIENTS, d), jnp.float32),
                              jax.ShapeDtypeStruct((COALITIONS,), jnp.int32))
    text = lowered.compile().as_text()
    calls = sum('custom_call_target="tpu_custom_call"' in line
                for line in text.splitlines())
    print(f"[kernels] pallas fused round N={CLIENTS} K={COALITIONS} D={d}: "
          f"{calls} tpu_custom_call ops, {stats.since(snap)}")
    if native:
        check(calls >= 2, "the pallas round holds no native TPU kernel "
              "(interpret mode on the chip?)")

    store = ModelStore(run["store"])
    w = _client_matrix(store.load(store.rounds()[-1]).global_params)
    ci = jnp.asarray([0, 4, 7], jnp.int32)
    got = fz.fused_round(w, ci, backend="pallas")
    want = fz.fused_round(w, ci, backend="xla")
    check(np.array_equal(got.assignment, want.assignment)
          and np.array_equal(got.new_center_idx, want.new_center_idx),
          f"same W: pallas assignment {got.assignment.tolist()} medoids "
          f"{got.new_center_idx.tolist()} vs xla {want.assignment.tolist()} "
          f"{want.new_center_idx.tolist()}")
    dtheta = _rel_err(got.theta, want.theta)
    dmed = _rel_err(got.med_d2, want.med_d2)
    check(dtheta <= THETA_RTOL, f"same W: |Δθ| {dtheta} > {THETA_RTOL}")
    check(dmed <= MED_RTOL, f"same W: medoid d² error {dmed} > {MED_RTOL}")
    print(f"[kernels] same W, pallas vs xla: assignment and medoids equal, "
          f"max |Δθ| / max |θ| {dtheta:.3e}, max |Δd²| / max d² {dmed:.3e}")


def _client_matrix(params) -> jax.Array:
    """(N, D) client weights around θ = ``params``, spread as one local
    epoch spreads them (‖δ_i − δ_j‖² ≈ 0.1 over the CNN's D)."""
    theta = pytree.flatten(params)
    delta = 2.5e-4 * jax.random.normal(jax.random.key(7),
                                       (CLIENTS, theta.shape[0]))
    return theta[None, :] + delta


def _rel_err(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def replay_phase(stats: CompileStats, run: dict, backend: str) -> None:
    """Round r of ``backend`` from ``run``'s own carry after round r − 1,
    for every r: the same assignment and medoids, θ within THETA_RTOL.  Two
    free-running federations cannot be compared round by round — local SGD
    carries a one-ulp difference in θ into ~1e-4 after one epoch — so each
    round is compared on the same inputs."""
    ref_store = ModelStore(run["store"])
    worst = 0.0
    for r in range(ROUNDS):
        rep = train_phase(
            stats, backend, f"{run['tag']}_{backend}_r{r}", rounds=r + 1,
            resume=None if r == 0 else (run["ckpt"], r - 1))
        a, b = ref_store.load(r), ModelStore(rep["store"]).load(r)
        check(np.array_equal(a.assignment, b.assignment),
              f"round {r}: assignment {run['tag']} {a.assignment.tolist()} "
              f"vs {rep['tag']} {b.assignment.tolist()}")
        ma = checkpoint.load(run["ckpt"], r)[0]["strategy"]["0000"]
        mb = checkpoint.load(rep["ckpt"], r)[0]["strategy"]["0000"]
        check(np.array_equal(ma, mb),
              f"round {r}: medoids {run['tag']} {np.asarray(ma).tolist()} "
              f"vs {rep['tag']} {np.asarray(mb).tolist()}")
        err = _rel_err(pytree.flatten(b.global_params),
                       pytree.flatten(a.global_params))
        check(err <= THETA_RTOL,
              f"round {r}: max |Δθ| / max |θ| {err} > {THETA_RTOL}")
        worst = max(worst, err)
    print(f"[agree {backend} vs {run['tag']}] rounds 0..{ROUNDS - 1}: "
          f"assignments and medoids "
          f"equal, max |Δθ| / max |θ| {worst:.3e} (limit {THETA_RTOL})")


def mesh_round_phase(stats: CompileStats, mesh: str) -> None:
    """The coalition round as a ``Federation`` on ``mesh`` runs it, on the
    ``pallas`` and ``xla`` backends, against the same round dense on one
    chip, all on one W built whole on device 0 (as the engine builds it)."""
    from repro.core.server import Federation, FederationConfig
    from repro.models import zoo

    model = zoo.make_model("cnn")
    w = _client_matrix(model.init(jax.random.key(0)))

    def one_round(backend, mesh_spec):
        fed = Federation(model.loss_fn, lambda p: 0.0, FederationConfig(
            n_clients=CLIENTS, n_coalitions=COALITIONS, backend=backend,
            mesh=mesh_spec))
        state = fed.strategy.init_state(jax.random.key(1), w)
        step = jax.jit(fed.strategy.round)
        snap = stats.snapshot()
        t0 = time.perf_counter()
        res = jax.block_until_ready(step(w, state))
        t1 = time.perf_counter()
        jax.block_until_ready(step(w, state))
        print(f"[round {fed.strategy.backend.name}] first call "
              f"{t1 - t0:.3f} s, second {time.perf_counter() - t1:.6f} s, "
              f"{stats.since(snap)}")
        memory_report(fed.strategy.backend.name)
        return res

    dense = one_round("xla", None)
    for backend in ("pallas", "xla"):
        res = one_round(backend, mesh)
        check(np.array_equal(res.metrics.assignment, dense.metrics.assignment)
              and np.array_equal(res.state.center_idx,
                                 dense.state.center_idx),
              f"{backend}@{mesh}: assignment "
              f"{res.metrics.assignment.tolist()} medoids "
              f"{res.state.center_idx.tolist()} vs dense "
              f"{dense.metrics.assignment.tolist()} "
              f"{dense.state.center_idx.tolist()}")
        err = _rel_err(res.theta, dense.theta)
        check(err <= THETA_RTOL,
              f"{backend}@{mesh}: max |Δθ| / max |θ| {err} > {THETA_RTOL}")
        print(f"[agree {backend}@{mesh} vs dense xla] assignment and medoids "
              f"equal, max |Δθ| / max |θ| {err:.3e} (limit {THETA_RTOL})")


def serve_phase(run: dict) -> None:
    """Serve routed batches from the run's store, hot-swapping to the
    newest round in between; one compilation throughout."""
    store = ModelStore(run["store"])
    rounds = store.rounds()
    apply_fn, _ = serve_cli.make_apply_fn("cnn", "", True)
    server = BatchServer(apply_fn, store.load(rounds[-2]))
    x = jnp.asarray(synthetic.digits(SERVE_BATCH, seed=2)[0])
    # every known client plus one stranger (routed to the global θ)
    ids = np.arange(SERVE_BATCH) % (CLIENTS + 1)
    ids = np.where(ids == CLIENTS, -1, ids)
    for stage in ("before swap", "after swap"):
        lat = []
        for _ in range(SERVE_REPEATS):
            t0 = time.perf_counter()
            out = jax.block_until_ready(server.serve(ids, x))
            lat.append(round((time.perf_counter() - t0) * 1e3, 3))
        check(out.shape == (SERVE_BATCH, 10), f"served shape {out.shape}")
        check(bool(jnp.all(jnp.isfinite(out))), "served logits not finite")
        rows = server.routing.model_rows(ids)
        for m in np.unique(rows):
            want = apply_fn(server.model_params(int(m)), x)[rows == m]
            got = out[rows == m]
            err = float(jnp.max(jnp.abs(got - want)))
            check(err <= SERVE_RTOL * float(jnp.max(jnp.abs(want))),
                  f"served model row {m}: max |Δ logits| {err}")
        print(f"[serve] round {server.round} {stage}: batch {SERVE_BATCH}, "
              f"batch ms {lat}, compile_count {server.compile_count}")
        if stage == "before swap":
            check(server.poll(store) and server.round == rounds[-1],
                  "hot swap to the newest round did not happen")
    check(server.compile_count == 1,
          f"compile_count {server.compile_count} across a hot swap, not 1")


def memory_report(tag: str) -> None:
    for d in jax.devices():
        m = d.memory_stats()
        held = ("not reported" if m is None else
                f"in use {m.get('bytes_in_use')} B, peak "
                f"{m.get('peak_bytes_in_use')} B")
        print(f"[memory {tag}] {d}: {held}")


def main(argv=None, *, require_tpu: bool = True) -> None:
    """``require_tpu=False`` lets a CPU rehearsal run every phase (kernels in
    interpret mode); the command line always requires the TPU."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data=4 mesh round vs dense")
    args = ap.parse_args(argv)
    print(f"[cache] {compile_cache.enable()}")
    stats = CompileStats()
    device = device_phase(args.chips, require_tpu)
    shutil.rmtree(OUT, ignore_errors=True)
    if args.chips == 4:
        mesh_round_phase(stats, "data=4")
    else:
        run = train_phase(stats, "pallas", "pallas")
        check(run["acc"][-1] > ACC_MIN, f"last-round accuracy "
              f"{run['acc'][-1]} <= {ACC_MIN}")
        kernel_phase(stats, run, native=device["platform"] == "tpu")
        replay_phase(stats, run, "xla")
        serve_phase(run)
    print(f"[total] {stats.since((0.0, 0, 0))}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
