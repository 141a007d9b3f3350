"""Benchmark harness — one entry per paper table/figure plus kernel
microbenchmarks.  Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run            # quick suite (CPU)
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale figures
  PYTHONPATH=src python -m benchmarks.run --only coalition_round --json
                                     # CI perf tier -> BENCH_round.json
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import compile_cache

#: structured results (filled as benches run; dumped by --json)
_JSON: dict = {}


def _timeit_full(fn, *args, reps: int = 5) -> tuple[float, float]:
    """(steady us/call, first-call us) — compile time recorded, not timed in.

    Every steady rep blocks: with async dispatch a loop of un-synced calls
    only measures enqueue time and lets queued reps under-report (the old
    bug — one sync at the end timed reps-1 dispatches plus a single
    execution).  The first call is trace + XLA compile + one execution; it
    is only a genuine compile measurement if ``fn`` has not run on these
    avals yet (call ``_timeit_full`` before any warm-up of ``fn``).
    """
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))             # compile + first run
    compile_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6, compile_us


def _timeit_best(fn, *args, reps: int = 5) -> tuple[float, float]:
    """(best-of-reps us/call, first-call us) — min instead of mean.

    For memory-bound single-shot kernels where a scheduler hiccup on one rep
    shifts a ratio gate; the min is the standard low-noise estimator there.
    """
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))             # compile + first run
    compile_us = (time.perf_counter() - t0) * 1e6
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6, compile_us


def _timeit(fn, *args, reps: int = 5) -> float:
    """Steady-state us/call, compile excluded (see :func:`_timeit_full`)."""
    return _timeit_full(fn, *args, reps=reps)[0]


def _coalition_round_stats(d: int, reps: int) -> dict:
    """Composed-vs-fused Algorithm 1 server step at N=10, K=3.

    Times both paths and traces both to count full sweeps over the (N, D)
    weight matrix (repro.core.instrument); the fused path must read W
    exactly twice.
    """
    from repro.core import coalitions, instrument
    from repro.core import fused as fused_mod

    w = jax.random.normal(jax.random.key(0), (10, d), jnp.float32)
    state = coalitions.init_centers(jax.random.key(1), w, 3)
    composed = jax.jit(
        lambda w_, s: coalitions.run_round(w_, s, fused=False).theta)
    fused = jax.jit(
        lambda w_, s: coalitions.run_round(w_, s, fused=True).theta)
    # time before any other call so the first-call number really is trace +
    # compile (the bitwise-agreement check reuses the now-warm executables)
    us_c, compile_us_c = _timeit_full(composed, w, state, reps=reps)
    us_f, compile_us_f = _timeit_full(fused, w, state, reps=reps)
    err = float(jnp.max(jnp.abs(composed(w, state) - fused(w, state))))
    passes = {}
    for name, fn in (("composed", composed), ("fused", fused)):
        with instrument.count_w_passes() as p:
            jax.make_jaxpr(lambda w_, s: coalitions.run_round(
                w_, s, fused=(name == "fused")).theta)(w, state)
        passes[name] = p()
    return {"n": 10, "d": d, "k": 3,
            "chunk": fused_mod.resolve_chunk(None, d),
            "composed_us": us_c, "fused_us": us_f,
            "composed_compile_us": compile_us_c,
            "fused_compile_us": compile_us_f,
            "speedup": us_c / us_f,
            "composed_w_passes": passes["composed"],
            "fused_w_passes": passes["fused"],
            "max_abs_err": err}


def bench_coalition_round() -> tuple[float, float]:
    """Fused Algorithm 1 server step at the paper's scale (N=10, D=582k);
    derived = speedup of the two-pass fused round over the composed path."""
    r = _coalition_round_stats(d=582_026, reps=5)
    _JSON.setdefault("coalition_round", {})["d582k"] = r
    return r["fused_us"], r["speedup"]


def bench_coalition_round_d8m() -> tuple[float, float]:
    """Framework-scale round (D=8M, HBM-bandwidth-bound regime); derived =
    passes over W of the fused path (must be exactly 2)."""
    r = _coalition_round_stats(d=8_000_000, reps=3)
    _JSON.setdefault("coalition_round", {})["d8m"] = r
    assert r["fused_w_passes"] == 2, \
        f"two-pass contract broken: fused round reads W {r['fused_w_passes']}x"
    return r["fused_us"], float(r["fused_w_passes"])


def bench_pairwise_kernel() -> tuple[float, float]:
    from repro.kernels import ops, ref

    w = jax.random.normal(jax.random.key(0), (10, 582_026), jnp.float32)
    us = _timeit(ops.pairwise_sq_dists, w)
    err = float(jnp.max(jnp.abs(ops.pairwise_sq_dists(w)
                                - ref.pairwise_sq_dists(w))))
    rel = err / float(jnp.max(ref.pairwise_sq_dists(w)))
    return us, rel


def bench_segment_sum() -> tuple[float, float]:
    from repro.kernels import ops, ref

    oh = jax.nn.one_hot(jax.random.randint(jax.random.key(1), (10,), 0, 3), 3).T
    w = jax.random.normal(jax.random.key(0), (10, 582_026), jnp.float32)
    us = _timeit(ops.segment_sum, oh, w)
    err = float(jnp.max(jnp.abs(ops.segment_sum(oh, w) - ref.segment_sum(oh, w))))
    return us, err


def bench_flash_attention() -> tuple[float, float]:
    from repro.kernels import ops, ref

    q = jax.random.normal(jax.random.key(0), (1, 8, 256, 64), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (1, 2, 256, 64), jnp.float32)
    us = _timeit(lambda: ops.flash_attention(q, k, v))
    err = float(jnp.max(jnp.abs(ops.flash_attention(q, k, v)
                                - ref.attention(q, k, v))))
    return us, err


def bench_fig(regime: str, full: bool) -> tuple[float, float]:
    from benchmarks.paper_figures import run_regime

    kw = (dict(rounds=15, n_train=10000, n_test=2000, local_epochs=2)
          if full else dict(rounds=5, n_train=3000, n_test=600,
                            local_epochs=1))
    t0 = time.perf_counter()
    r = run_regime(regime, clients=10, coalitions=3, batch_size=10, lr=0.05,
                   seed=0, **kw)
    us_per_round = (time.perf_counter() - t0) / kw["rounds"] * 1e6
    return us_per_round, r["final_gap"]


def bench_federation_engines() -> tuple[float, float]:
    """Scanned (lax.scan) vs host-loop federation engine, same strategy/seed.

    A 100-round coalition federation over a small least-squares model — per
    round compute is tiny, so the per-round host round-trips and dispatch the
    python loop pays (and the scan engine eliminates) dominate (~3x on this
    container; parity at paper-CNN scale where CPU compute swamps dispatch).
    Returns (us per scanned run, speedup of scan over the python loop);
    execution time only, compile excluded for both engines.
    """
    fed, params, cd = _tiny_federation(100, "coalition")
    key = jax.random.key(1)

    times, compiles = {}, {}
    for engine in ("scan", "python"):
        t0 = time.perf_counter()
        fed.run(params, cd, key, engine=engine)          # compile
        compiles[engine] = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        for _ in range(3):
            fed.run(params, cd, key, engine=engine)
        times[engine] = (time.perf_counter() - t0) / 3 * 1e6
    _JSON["federation_engines"] = {
        "rounds": 100,
        "scan_us": times["scan"], "python_us": times["python"],
        "scan_compile_us": compiles["scan"],
        "python_compile_us": compiles["python"],
        "speedup": times["python"] / times["scan"]}
    return times["scan"], times["python"] / times["scan"]


def _tiny_federation(rounds: int, method: str, sim_cfg=None):
    """A small least-squares federation (shared by the engine benchmarks)."""
    from repro import sim
    from repro.core.client import ClientConfig
    from repro.core.server import Federation, FederationConfig

    n_clients, n_local, dim = 8, 20, 16
    kx, kw, kt = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(kx, (n_clients, n_local, dim))
    w_true = jax.random.normal(kw, (dim,))
    y = x @ w_true + 0.1 * jax.random.normal(kt, (n_clients, n_local))
    cd = {"x": x, "y": y}

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    xe = x.reshape(-1, dim)[:50]
    ye = (x @ w_true).reshape(-1)[:50]
    cfg = FederationConfig(
        n_clients=n_clients, n_coalitions=3, rounds=rounds, method=method,
        client=ClientConfig(epochs=1, batch_size=10, lr=0.01),
        sim=sim_cfg if sim_cfg is not None else sim.SimConfig())
    fed = Federation(loss_fn, lambda p: -jnp.mean((xe @ p["w"] - ye) ** 2),
                     cfg)
    return fed, {"w": jnp.zeros((dim,))}, cd


def bench_federation_scale() -> tuple[float, float]:
    """Fleet-size decoupling: cohort-mode federation at a fixed cohort width
    C=16 while the fleet grows N ∈ {64, 1024, 65536, 1048576}.

    The model is a two-layer regression sized to paper scale (D ≈ 8.5M,
    ~34 MB fp32 per client) so the O(C·D) cohort buffers dominate anything
    O(N): the hierarchical availability-weighted sampler
    (repro.sim.cohort) plus the gather/scatter cohort view keep the jitted
    round loop blind to N, so both us/round and live bytes must stay flat
    (±20%, gated in CI) from N=64 to N=2^20.  Two reference rows ride
    along at the largest dense-feasible width (n_clients=64, no cohort):
    the plain dense round and the same run on a ``data``-sharded mesh
    (every local device; psum-identity on 1 device), gated sharded ≤
    dense wall-clock with a 15% scheduler-noise allowance.

    Live bytes are sampled host-side at every round-record emit
    (``jax.live_arrays()`` — the engine carry, fleet tables, and cohort
    schedule are alive there; the W transient is not).  Returns (us per
    cohort round at N=2^20, step-time ratio N=2^20 / N=64).
    """
    import gc

    from repro import sim
    from repro.core.client import ClientConfig
    from repro.core.server import Federation, FederationConfig
    from repro.obs.ledger import Sink

    C, K, rounds, in_dim, h = 16, 3, 3, 64, 131_072
    n_dense = 64                      # largest dense-feasible fleet at this D
    kx, ky, k1, k2 = jax.random.split(jax.random.key(0), 4)
    cd = {"x": jax.random.normal(kx, (n_dense, 4, in_dim)),
          "y": jax.random.normal(ky, (n_dense, 4))}
    init = {"w1": 0.1 * jax.random.normal(k1, (in_dim, h)),
            "w2": 0.1 * jax.random.normal(k2, (h,))}
    d_model = in_dim * h + h

    def loss_fn(params, batch):
        pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - batch["y"]) ** 2)

    xe = cd["x"][0]
    ye = cd["y"][0]

    def eval_fn(params):
        return -loss_fn(params, {"x": xe, "y": ye})

    class _LiveBytes(Sink):
        def __init__(self):
            self.peak = 0

        def emit(self, record):
            if record.get("kind") == "round":
                self.peak = max(self.peak, sum(
                    a.nbytes for a in jax.live_arrays()))

    def measure(n_clients, fleet_size, mesh):
        cfg = FederationConfig(
            n_clients=n_clients, n_coalitions=K, rounds=rounds,
            method="coalition",
            client=ClientConfig(epochs=1, batch_size=4, lr=0.05),
            fleet_size=fleet_size, mesh=mesh,
            sim=sim.SimConfig(fleet="lognormal-edge"))
        fed = Federation(loss_fn, eval_fn, cfg)
        key = jax.random.key(1)
        t0 = time.perf_counter()
        fed.run(init, cd, key)                           # compile + schedule
        compile_us = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        fed.run(init, cd, key)
        best = time.perf_counter() - t0
        mem = _LiveBytes()                 # doubles as the second timing rep
        t0 = time.perf_counter()
        fed.run(init, cd, key, sink=mem)
        best = min(best, time.perf_counter() - t0)
        del fed
        gc.collect()
        return {"us_per_round": best / rounds * 1e6,
                "compile_us": compile_us, "live_bytes": mem.peak}

    out = {"cohort_size": C, "d": d_model, "rounds": rounds, "sweep": {}}
    for n in (64, 1024, 65_536, 1_048_576):
        row = measure(C, n, None)
        out["sweep"][str(n)] = row
        print(f"# scale[N={n}] us/round={row['us_per_round']:.0f} "
              f"live_MB={row['live_bytes'] / 1e6:.0f}", flush=True)
    out["dense"] = {"n": n_dense, **measure(n_dense, None, None)}
    mesh_spec = f"data={len(jax.devices())}"
    out["sharded"] = {"n": n_dense, "mesh": mesh_spec,
                      **measure(n_dense, None, mesh_spec)}
    for kind in ("dense", "sharded"):
        row = out[kind]
        print(f"# scale[{kind} n={n_dense}] "
              f"us/round={row['us_per_round']:.0f} "
              f"live_MB={row.get('live_bytes', 0) / 1e6:.0f}", flush=True)
    _JSON["federation_scale"] = out
    us_1m = out["sweep"]["1048576"]["us_per_round"]
    return us_1m, us_1m / out["sweep"]["64"]["us_per_round"]


def bench_federation_sketch() -> tuple[float, float]:
    """Sketched vs exact coalition geometry at framework scale (D=8M).

    The exact side times the two full-width distance sweeps the sketch
    replaces (assignment d2c against the pinned centers + the medoid-electing
    d2 against barycenters, barycenters precomputed outside the timed
    region).  The sketched side times the countsketch build (one
    memory-bound pass over W) *plus* the entire sketch-space geometry
    (``fused.sketch_stage``) — i.e. everything up to the point where the two
    paths hand identical (assignment, med_d2) roles to the barycenter
    matmul.  Swept over S ∈ {64, 256, 1024} on a 3-cluster fleet; CI gates
    assignment agreement ≥ 0.95 at S=1024, speedup ≥ 3x at D=8M, and the
    sketched fused round tracing exactly 2 full W passes (1 with the sketch
    in hand).  Returns (sketched us at S=1024, speedup at S=1024).
    """
    from repro.core import fused as fz
    from repro.core import instrument
    from repro.core import sketch as sketch_mod

    n, d, k = 10, 8_000_000, 3
    owner = jnp.arange(n) % k
    mu = jnp.asarray([-4.0, 0.0, 4.0], jnp.float32)[owner][:, None]
    w = mu + 0.5 * jax.random.normal(jax.random.key(0), (n, d), jnp.float32)
    ci = jnp.asarray([0, 1, 2], jnp.int32)          # one center per cluster
    backend = fz.bk.get_backend("xla")
    b = fz.fused_round(w, ci).barycenters           # (K, D), outside timing

    def exact_geom(w_, b_):
        centers = jnp.take(w_, ci, axis=0)
        d2c = backend.sq_dists_to_points(w_, centers)
        return fz.pin_assignment(d2c, ci), backend.sq_dists_to_points(w_, b_)

    exact = jax.jit(exact_geom)
    exact_us, exact_compile_us = _timeit_best(exact, w, b, reps=5)
    ex_assign = exact(w, b)[0]

    out = {"n": n, "d": d, "k": k, "exact_us": exact_us,
           "exact_compile_us": exact_compile_us, "sweep": {}}
    for s in (64, 256, 1024):
        skr = sketch_mod.make_sketcher("countsketch", dim=s)
        sketched = jax.jit(lambda w_, _sk=skr: fz.sketch_stage(
            backend, sketch_mod.sketch_matrix(_sk, w_), ci))
        us, compile_us = _timeit_best(sketched, w, reps=5)
        agreement = float(jnp.mean(sketched(w)[0] == ex_assign))
        with instrument.count_w_passes() as p:
            jax.make_jaxpr(lambda w_, _sk=skr: fz.fused_round(
                w_, ci, sketcher=_sk).theta)(w)
        row = {"s": s, "sketch_us": us, "sketch_compile_us": compile_us,
               "speedup": exact_us / us, "agreement": agreement,
               "sketched_w_passes": p()}
        out["sweep"][str(s)] = row
        print(f"# sketch[S={s}] us={us:.0f} speedup={row['speedup']:.2f} "
              f"agreement={agreement:.3f} w_passes={p()}", flush=True)
    _JSON["federation_sketch"] = out
    top = out["sweep"]["1024"]
    return top["sketch_us"], top["speedup"]


def bench_coalition_vs_fedavg_under_stragglers() -> tuple[float, float]:
    """The IoT-substrate benchmark: both aggregation rules on the
    ``semi_async`` engine over the same flaky cellular fleet.  Prints the
    per-round simulated wall-clock and WAN bytes for each rule as ``#``
    comment rows, and returns (us per coalition run, WAN-byte saving of the
    hierarchical coalition schedule over flat FedAvg on the rounds that
    actually ran).
    """
    from repro import sim

    sim_cfg = sim.SimConfig(fleet="cellular-flaky", seed=0,
                            staleness_alpha=0.5)
    totals, us = {}, 0.0
    for method in ("coalition", "fedavg"):
        fed, params, cd = _tiny_federation(12, method, sim_cfg)
        key = jax.random.key(1)
        fed.run(params, cd, key, engine="semi_async")            # compile
        t0 = time.perf_counter()
        _, hist = fed.run(params, cd, key, engine="semi_async")
        if method == "coalition":
            us = (time.perf_counter() - t0) * 1e6
        totals[method] = sum(hist.wan_bytes)
        print(f"# stragglers[{method}] sim_time_s/round="
              f"{[round(t, 2) for t in hist.sim_times]}")
        print(f"# stragglers[{method}] wan_kB/round="
              f"{[round(b / 1e3, 2) for b in hist.wan_bytes]}")
        print(f"# stragglers[{method}] participants/round="
              f"{[sum(r) for r in hist.participation]}")
    return us, totals["fedavg"] / totals["coalition"]


def bench_energy_constrained_stragglers() -> tuple[float, float]:
    """Wall-clock-to-accuracy under an energy-constrained flaky fleet: both
    aggregation rules on the ``event_driven`` continuous-time engine over
    the same cellular fleet with a finite per-device energy budget.
    Devices report whenever their train/transmit cycle completes, deplete
    their budget per cycle, and retire when they can no longer afford one.
    Returns (us per coalition run, WAN-byte saving of the hierarchical
    schedule over flat FedAvg on the cycles that actually delivered — note
    the saving erodes vs the round-synchronous engine, since a singleton
    completion cohort ships min(K, 1) barycenters either way); the full
    per-rule wall-clock-to-accuracy trajectory lands in the ``--json``
    artifact.
    """
    from repro import sim

    sim_cfg = sim.SimConfig(fleet="cellular-flaky", seed=0,
                            staleness_alpha=0.5, energy_budget=6.0,
                            max_events=24)
    stats, us = {}, 0.0
    for method in ("coalition", "fedavg"):
        fed, params, cd = _tiny_federation(12, method, sim_cfg)
        key = jax.random.key(1)
        fed.run(params, cd, key, engine="event_driven")          # compile
        t0 = time.perf_counter()
        _, hist = fed.run(params, cd, key, engine="event_driven")
        if method == "coalition":
            us = (time.perf_counter() - t0) * 1e6
        dead = np.asarray(hist.trace.energy_exhausted)
        total_t = hist.event_times[-1]       # raw: the CI gate asserts > 0
        stats[method] = {
            "final_acc": hist.test_acc[-1],
            "sim_time_s": total_t,
            "acc_trajectory": hist.test_acc,
            "event_times": hist.event_times,
            "wan_bytes": sum(hist.wan_bytes),
            "deliveries": float(np.asarray(hist.trace.participation).sum()),
            "energy_spent_j": float(
                np.asarray(hist.trace.energy_spent)[-1].sum()),
            "devices_exhausted": int(dead[-1].sum()),
        }
        print(f"# energy[{method}] acc={stats[method]['final_acc']:.4f} "
              f"sim_t={total_t:.1f}s "
              f"wan_kB={stats[method]['wan_bytes'] / 1e3:.1f} "
              f"exhausted={stats[method]['devices_exhausted']}"
              f"/{fed.cfg.n_clients}")
    _JSON["energy_stragglers"] = stats
    return us, stats["fedavg"]["wan_bytes"] / stats["coalition"]["wan_bytes"]


def bench_correlated_skew() -> tuple[float, float]:
    """The fleet-aware scenario benchmark: does weight-driven coalition
    formation recover minority-label knowledge that availability/deadline
    censoring keeps dropping?

    Both aggregation rules run the ``semi_async`` engine over the same
    ``cellular-flaky`` fleet while the ``correlated-skew`` scenario sweeps
    the fleet-data coupling ``rho ∈ {0, 0.5, 1}``: at rho=0 the label-skewed
    Dirichlet shards land on devices independently (today's decoupled
    sampling, bit-for-bit); at rho=1 the weakest devices — the ones the
    deadline and the availability process censor — hold the most-skewed
    shards.  A linear softmax probe on the synthetic digits keeps the runs
    CI-sized while still exposing per-label recall.  Reports final accuracy,
    per-label recall, and WAN bytes per rule per rho in the ``--json``
    artifact; returns (us per coalition run at rho=1, coalition - fedavg
    final-accuracy gap at rho=1).
    """
    from repro import sim
    from repro.core.client import ClientConfig
    from repro.core.server import Federation, FederationConfig
    from repro.data import loader, synthetic

    n_clients, n_classes, rounds = 10, 10, 12
    (xtr, ytr) = synthetic.digits(2000, seed=0)
    (xte, yte) = synthetic.digits(400, seed=1)
    xtr_f = xtr.reshape(len(xtr), -1)
    xte_j = jnp.asarray(xte.reshape(len(xte), -1))
    yte_j = jnp.asarray(yte)

    def loss_fn(params, batch):
        logits = batch["x"] @ params["w"] + params["b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(
            logp, batch["y"][:, None].astype(jnp.int32), axis=1))

    def eval_fn(params):
        pred = jnp.argmax(xte_j @ params["w"] + params["b"], axis=1)
        return jnp.mean((pred == yte_j).astype(jnp.float32))

    def per_label_recall(params) -> list[float]:
        pred = np.asarray(jnp.argmax(xte_j @ params["w"] + params["b"],
                                     axis=1))
        yt = np.asarray(yte_j)
        return [float(np.mean(pred[yt == c] == c)) for c in range(n_classes)]

    init = {"w": jnp.zeros((xtr_f.shape[1], n_classes), jnp.float32),
            "b": jnp.zeros((n_classes,), jnp.float32)}
    out: dict = {"scenario": {}, "coalition": {}, "fedavg": {}}
    us = 0.0
    for rho in (0.0, 0.5, 1.0):
        scn = sim.make_scenario("correlated-skew", ytr, n_clients,
                                fleet="cellular-flaky", regime="dirichlet",
                                # sim_seed=2: a fleet draw whose chance
                                # correlation with the seed-0 Dirichlet
                                # skew ranks is ~0, so the rho sweep
                                # starts from a genuinely decoupled base
                                rho=rho, seed=0, sim_seed=2, alpha=0.3)
        out["scenario"][f"{rho}"] = {
            "permutation": scn.metadata["permutation"],
            "spearman": scn.metadata["spearman"]}
        cd = jax.tree.map(jnp.asarray,
                          loader.client_datasets(xtr_f, ytr,
                                                 scn.index_matrix))
        for method in ("coalition", "fedavg"):
            cfg = FederationConfig(
                n_clients=n_clients, n_coalitions=3, rounds=rounds,
                method=method, engine="semi_async",
                client=ClientConfig(epochs=2, batch_size=20, lr=0.2),
                sim=sim.SimConfig(fleet="cellular-flaky", seed=2,
                                  deadline=4.0, scenario="correlated-skew",
                                  rho=rho))
            fed = Federation(loss_fn, eval_fn, cfg)
            key = jax.random.key(1)
            fed.run(init, cd, key)                       # compile
            t0 = time.perf_counter()
            gp, hist = fed.run(init, cd, key)
            if method == "coalition" and rho == 1.0:
                us = (time.perf_counter() - t0) * 1e6
            recall = per_label_recall(gp)
            out[method][f"{rho}"] = {
                "final_acc": hist.test_acc[-1],
                "per_label_recall": recall,
                "min_label_recall": min(recall),
                "wan_bytes": sum(hist.wan_bytes),
                "mean_participation": float(
                    np.mean(hist.participation))}
            print(f"# skew[{method} rho={rho}] "
                  f"acc={hist.test_acc[-1]:.4f} "
                  f"min_recall={min(recall):.3f} "
                  f"wan_kB={sum(hist.wan_bytes) / 1e3:.1f} "
                  f"spearman={scn.metadata['spearman']:+.2f}")
    _JSON["correlated_skew"] = out
    gap = (out["coalition"]["1.0"]["final_acc"]
           - out["fedavg"]["1.0"]["final_acc"])
    return us, gap


def bench_attack() -> tuple[float, float]:
    """Byzantine robustness: every aggregation rule under a 20% scale_update
    attack (boost=100) on the least-squares probe, plus a DP-noised coalition
    row with its moments-accountant epsilon.

    Ten clients, two compromised (``adv_frac=0.2``), six rounds.  Each rule
    runs once clean and once attacked from the same seed; the eval is the
    negative MSE against the *noiseless* targets, so the reported number is
    honest-model quality — a rule that averages the boosted updates into θ
    craters it.  The ``--json`` artifact carries per-rule clean/attacked
    evals, the final quarantine fraction and contamination bound for the
    coalition rules, and the DP row (clip=1, sigma=0.8) with its composed
    epsilon; CI gates the robust rules (trimmed mean, top-m coalitions)
    beating plain FedAvg under attack, coalition quarantine converging to 0,
    and the epsilon being finite.  Returns (us per attacked coalition run,
    attacked-eval margin of fedavg_trimmed over fedavg).
    """
    from repro import sim
    from repro.core import strategies as strat_mod
    from repro.core.client import ClientConfig
    from repro.core.server import Federation, FederationConfig

    n_clients, n_local, dim, rounds, k = 10, 12, 8, 6, 3
    kx, kw, kt = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(kx, (n_clients, n_local, dim))
    w_true = jax.random.normal(kw, (dim,))
    y = x @ w_true + 0.1 * jax.random.normal(kt, (n_clients, n_local))
    cd = {"x": x, "y": y}

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    xe = x.reshape(-1, dim)[:60]
    ye = (x @ w_true).reshape(-1)[:60]

    def eval_fn(params):
        return -jnp.mean((xe @ params["w"] - ye) ** 2)

    init = {"w": jnp.zeros((dim,))}
    rules = {"fedavg": {}, "fedavg_trimmed": {"trim": 2},
             "coalition": {}, "coalition_topk": {"top_m": 2}}

    def run(method, attack=None, adv_frac=0.0, dp=None):
        cfg = FederationConfig(
            n_clients=n_clients, n_coalitions=k, rounds=rounds,
            method=method,
            client=ClientConfig(epochs=1, batch_size=6, lr=0.05,
                                **(dp or {})),
            adv_frac=adv_frac, sim=sim.SimConfig(seed=0))
        strategy = strat_mod.make_strategy(
            method, n_clients=n_clients, n_coalitions=k, **rules[method])
        fed = Federation(loss_fn, eval_fn, cfg, strategy=strategy,
                         attack=attack)
        t0 = time.perf_counter()
        _, hist = fed.run(init, cd, jax.random.key(1))
        return hist, (time.perf_counter() - t0) * 1e6

    boosted = sim.make_attack("scale_update", boost=100.0)
    out: dict = {"n": n_clients, "k": k, "rounds": rounds,
                 "attack": "scale_update", "boost": 100.0, "adv_frac": 0.2,
                 "rules": {}}
    us = 0.0
    for method in rules:
        clean, _ = run(method)
        hist, dt = run(method, attack=boosted, adv_frac=0.2)
        if method == "coalition":
            us = dt
        row = {"clean_eval": clean.test_acc[-1],
               "attacked_eval": hist.test_acc[-1],
               "n_adversaries": int(np.asarray(hist.adversary[-1]).sum()),
               "final_quarantine": hist.quarantine[-1],
               "final_contamination": hist.contamination[-1]}
        out["rules"][method] = row
        print(f"# attack[{method}] clean={row['clean_eval']:.4f} "
              f"attacked={row['attacked_eval']:.4f} "
              f"quarantine={row['final_quarantine']:.2f}", flush=True)
    from repro.obs import privacy

    dp_hist, _ = run("coalition", dp=dict(dp_clip=1.0, dp_sigma=0.8))
    eps = privacy.gaussian_epsilon(0.8, rounds + 1)
    out["dp"] = {"dp_clip": 1.0, "dp_sigma": 0.8,
                 "dp_epsilon": eps if np.isfinite(eps) else None,
                 "final_eval": dp_hist.test_acc[-1]}
    print(f"# attack[dp coalition] eval={out['dp']['final_eval']:.4f} "
          f"epsilon={eps:.2f}", flush=True)
    _JSON["attack"] = out
    margin = (out["rules"]["fedavg_trimmed"]["attacked_eval"]
              - out["rules"]["fedavg"]["attacked_eval"])
    return us, margin


def bench_serve() -> tuple[float, float]:
    """The producer/consumer serving path: a coalition federation publishes
    round snapshots into a ModelStore, a BatchServer answers coalition-routed
    batched queries from them and hot-swaps each newer round.  Measures
    serving throughput (queries/s, routed through per-coalition barycenters
    with the global-θ fallback in the batch) and swap latency (disk load +
    install of a newer round), and asserts the two serving invariants: the
    forward never recompiles across swaps, and the served round is the
    store's latest.  Returns (us per served batch, queries/s); the full
    stats land in the ``--json`` artifact as ``serve``.
    """
    import tempfile

    from repro.serve import BatchServer, ModelStore

    fed, params, cd = _tiny_federation(12, "coalition")
    store = ModelStore(tempfile.mkdtemp(prefix="bench-serve-"))
    fed.run(params, cd, jax.random.key(1), snapshot_every=2, store=store)
    rounds = store.rounds()

    def apply_fn(p, x):
        return x @ p["w"]

    server = BatchServer(apply_fn, store.load(rounds[0]))
    batch = 256
    n = fed.cfg.n_clients
    ids = np.arange(batch) % (n + 1)
    ids = np.where(ids == n, -1, ids)        # exercise the global fallback
    x = jax.random.normal(jax.random.key(2), (batch, 16), jnp.float32)

    us = _timeit(lambda: server.serve(ids, x))
    compiles_before = server.compile_count
    t0 = time.perf_counter()
    for r in rounds[1:]:
        server.swap(store.load(r))
    swap_ms = (time.perf_counter() - t0) / (len(rounds) - 1) * 1e3
    out = np.asarray(server.serve(ids, x))
    assert server.compile_count == compiles_before, \
        "hot swap recompiled the serving forward"
    assert server.round == store.latest_round()
    # routed answers come from the latest round's coalition barycenters
    snap = store.load()
    from repro.core import pytree as pt

    routed_bitexact = True
    for q in range(n):
        k = int(snap.assignment[q])
        direct = apply_fn(pt.unflatten(snap.barycenters[k],
                                       snap.global_params), x)[q]
        routed_bitexact &= bool(jnp.array_equal(out[q], direct))
    assert routed_bitexact, "routed serve drifted from the barycenter forward"
    qps = batch / (us / 1e6)
    _JSON["serve"] = {
        "batch": batch, "n_models": int(snap.barycenters.shape[0]) + 1,
        "published_rounds": rounds, "served_round": server.round,
        "latest_round": store.latest_round(),
        "queries_per_s": qps, "us_per_batch": us, "swap_ms": swap_ms,
        "hot_swaps": len(rounds) - 1, "compile_count": server.compile_count,
        "routed_bitexact": routed_bitexact,
    }
    return us, qps


def bench_comm_cost() -> tuple[float, float]:
    from benchmarks.comm_cost import table

    t0 = time.perf_counter()
    rows = table()
    return (time.perf_counter() - t0) * 1e6, rows[0]["wan_savings_x"]


def bench_decode_throughput() -> tuple[float, float]:
    from repro.configs import get, reduced
    from repro.models import transformer as tf

    cfg = reduced(get("starcoder2-7b"))
    params = tf.init(jax.random.key(0), cfg)
    cache = tf.init_cache(cfg, 4, 64)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 16), 0,
                                          cfg.vocab)}
    _, cache = tf.prefill(params, cfg, batch, cache)
    tok = jnp.zeros((4,), jnp.int32)
    fn = jax.jit(lambda p, t, c: tf.decode_step(p, cfg, t, c)[0])
    us = _timeit(fn, params, tok, cache)
    return us, 4.0 / (us / 1e6)                  # tokens/s


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale figure runs (slow)")
    ap.add_argument("--skip-figs", action="store_true")
    ap.add_argument("--only", default=None, metavar="SUBSTR",
                    help="run only benches whose name contains SUBSTR")
    ap.add_argument("--json", nargs="?", const="BENCH_round.json",
                    default=None, metavar="PATH",
                    help="write structured results (default BENCH_round.json)"
                         " so the perf trajectory accrues per PR")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the bench run "
                         "here (view in Perfetto / TensorBoard profile)")
    return ap


def main() -> None:
    args = build_parser().parse_args()
    compile_cache.enable()

    benches = [
        ("coalition_round_n10_d582k", bench_coalition_round),
        ("coalition_round_n10_d8m", bench_coalition_round_d8m),
        ("kernel_pairwise_dist", bench_pairwise_kernel),
        ("kernel_segment_sum", bench_segment_sum),
        ("kernel_flash_attention", bench_flash_attention),
        ("federation_scan_vs_python", bench_federation_engines),
        ("federation_scale", bench_federation_scale),
        ("federation_sketch", bench_federation_sketch),
        ("coalition_vs_fedavg_under_stragglers",
         bench_coalition_vs_fedavg_under_stragglers),
        ("coalition_vs_fedavg_energy_constrained",
         bench_energy_constrained_stragglers),
        ("coalition_vs_fedavg_correlated_skew", bench_correlated_skew),
        ("coalition_vs_fedavg_under_attack", bench_attack),
        ("serve_routed_batch", bench_serve),
        ("comm_cost_table", bench_comm_cost),
        ("decode_step_reduced", bench_decode_throughput),
    ]
    if not args.skip_figs:
        benches += [
            ("fig2_iid_gap", lambda: bench_fig("iid", args.full)),
            ("fig3_dirichlet_gap", lambda: bench_fig("dirichlet", args.full)),
            ("fig4_shard_gap", lambda: bench_fig("shard", args.full)),
        ]

    if args.only is not None:
        benches = [(n, f) for n, f in benches if args.only in n]

    import contextlib

    prof = (jax.profiler.trace(args.profile_dir) if args.profile_dir
            else contextlib.nullcontext())
    print("name,us_per_call,derived")
    failures = []
    with prof:
        for name, fn in benches:
            try:
                us, derived = fn()
                print(f"{name},{us:.1f},{derived:.6f}", flush=True)
            except Exception as e:  # pragma: no cover
                failures.append(name)
                print(f"{name},nan,ERROR:{type(e).__name__}:{e}", flush=True)

    if args.json is not None:
        _JSON["meta"] = {"backend": jax.default_backend(),
                         "jax": jax.__version__,
                         "platform": platform.platform(),
                         "failures": failures}
        with open(args.json, "w") as f:
            json.dump(_JSON, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
