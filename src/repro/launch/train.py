"""End-to-end training driver.

Modes:
  fl        (default) — the paper's experiment: federated training of the
            MNIST-surrogate CNN with FedAvg or coalition aggregation.
  pretrain  — data-parallel LM pretraining of a (reduced or full) assigned
            architecture on the synthetic token stream; runs on the local
            host mesh (CPU smoke scale) or a TPU slice unchanged.

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode fl --method coalition \
      --regime shard --rounds 20
  PYTHONPATH=src python -m repro.launch.train --mode fl --method coalition \
      --engine event_driven --fleet cellular-flaky --energy-budget 50 \
      --max-events 80
  PYTHONPATH=src python -m repro.launch.train --mode fl --method coalition \
      --engine semi_async --fleet cellular-flaky --scenario correlated-skew \
      --regime dirichlet --rho 1.0 --rounds 20
  PYTHONPATH=src python -m repro.launch.train --mode fl --method coalition \
      --rounds 10 --snapshot-dir /tmp/fl-store --snapshot-every 2 \
      --ckpt-dir /tmp/fl-ckpt --ckpt-every 5
  PYTHONPATH=src python -m repro.launch.train --mode fl --method coalition \
      --fleet-size 1048576 --clients 64 --fleet lognormal-edge --rounds 10
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train --mode fl --method coalition \
      --mesh data=8 --rounds 10
  PYTHONPATH=src python -m repro.launch.train --mode pretrain \
      --arch hymba-1.5b --reduced --steps 200
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sketch as sketch_mod
from repro.core import strategies
from repro.sim import attacks as sim_reg
from repro.data import partition
from repro.launch import compile_cache
from repro.models import zoo as zoo_mod


def _profiler(profile_dir: str | None):
    """``jax.profiler`` trace context when ``--profile-dir`` is set.

    Real-hardware time; the simulated-time view is ``--trace-out``
    (:mod:`repro.obs.timeline`).  Open the written trace in Perfetto or
    TensorBoard's profile plugin.
    """
    if profile_dir is None:
        return contextlib.nullcontext()
    return jax.profiler.trace(profile_dir)


# which strategies actually consume each CLI hyper-parameter — factories
# tolerate unknown kwargs (**_), so without this check a mismatched flag
# would be silently ignored while still looking applied
_EXTRA_CONSUMERS = {
    "top_m": ("coalition_topk",),
    "trim": ("fedavg_trimmed",),
    "client_weights": ("fedavg_weighted", "coalition", "coalition_topk"),
    "chunk": ("coalition", "coalition_topk"),
    "sketch": ("coalition", "coalition_topk"),
    "sketch_dim": ("coalition", "coalition_topk"),
}


def _finite(v: float, ndigits: int) -> float | None:
    """Round for JSON, mapping non-finite values to null (RFC 8259)."""
    return round(float(v), ndigits) if np.isfinite(v) else None


def _strategy_extras(args) -> dict:
    """Per-strategy hyper-parameters from the CLI (None = rule's default)."""
    extras = {}
    if args.top_m is not None:
        extras["top_m"] = args.top_m
    if args.trim is not None:
        extras["trim"] = args.trim
    if args.client_weights:
        extras["client_weights"] = jnp.asarray(
            [float(v) for v in args.client_weights.split(",")], jnp.float32)
    if args.chunk is not None:
        extras["chunk"] = args.chunk
    if args.sketch != "identity":
        extras["sketch"] = args.sketch
        if args.sketch_dim is not None:
            extras["sketch_dim"] = args.sketch_dim
    elif args.sketch_dim is not None:
        raise SystemExit("--sketch-dim requires --sketch rproj|countsketch "
                         "(identity has no sketch dimension)")
    for name in extras:
        if args.method not in _EXTRA_CONSUMERS[name]:
            raise SystemExit(
                f"--{name.replace('_', '-')} applies only to "
                f"{_EXTRA_CONSUMERS[name]}, not --method {args.method}")
    return extras


def run_fl(args) -> dict:
    from repro import sim
    from repro.core.client import ClientConfig
    from repro.core.server import Federation, FederationConfig
    from repro.data import loader, synthetic
    from repro.models import zoo

    # Fail fast on sharding/cohort flags, before any data touches memory:
    # a bad mesh spec or an undersized fleet should not cost a dataset load.
    if args.mesh is not None:
        from repro.launch import mesh as mesh_lib
        try:
            mesh_lib.parse_mesh(args.mesh)
        except ValueError as e:
            raise SystemExit(f"--mesh: {e}") from None
    if args.fleet_size is not None:
        if args.fleet_size < args.clients:
            raise SystemExit(f"--fleet-size {args.fleet_size} must be >= "
                             f"--clients {args.clients} (the per-round "
                             f"cohort is sampled from the fleet)")
        if args.engine not in ("scan", "python"):
            raise SystemExit("--fleet-size (cohort mode) requires --engine "
                             "scan or python")

    data = synthetic.mnist_idx()
    source = "mnist-idx"
    if data is None:
        data = (synthetic.digits(args.n_train, seed=0),
                synthetic.digits(args.n_test, seed=1))
        source = "synthetic-digits"
    (xtr, ytr), (xte, yte) = data
    # Joint fleet+data sampling: the scenario permutes which device holds
    # which shard (rho=0 == the independent sampling, bit-for-bit); the
    # engine re-samples the identical fleet from cfg.sim.fleet/seed.
    scn = sim.make_scenario(args.scenario, ytr, args.clients,
                            fleet=args.fleet, regime=args.regime,
                            rho=args.rho, seed=args.seed,
                            sim_seed=args.sim_seed)
    cd = jax.tree.map(jnp.asarray,
                      loader.client_datasets(xtr, ytr, scn.index_matrix))
    xte_j, yte_j = jnp.asarray(xte), jnp.asarray(yte)

    extras = _strategy_extras(args)
    strategy = strategies.make_strategy(
        args.method, n_clients=args.clients, n_coalitions=args.coalitions,
        backend=args.backend, **extras)
    cfg = FederationConfig(
        n_clients=args.clients, n_coalitions=args.coalitions,
        rounds=args.rounds, method=args.method,
        client=ClientConfig(epochs=args.local_epochs,
                            batch_size=args.batch_size, lr=args.lr,
                            dp_clip=args.dp_clip, dp_sigma=args.dp_sigma),
        backend=args.backend, engine=args.engine,
        attack=args.attack, adv_frac=args.adv_frac, rho_adv=args.rho_adv,
        fleet_size=args.fleet_size, mesh=args.mesh,
        sim=sim.SimConfig(fleet=args.fleet, participation=args.participation,
                          staleness_alpha=args.staleness,
                          deadline=args.deadline,
                          energy_budget=args.energy_budget,
                          max_events=args.max_events, seed=args.sim_seed,
                          scenario=args.scenario, rho=args.rho))
    model = zoo.make_model(args.model)
    params = model.init(jax.random.key(args.seed))
    store = None
    if args.snapshot_dir is not None:
        from repro.serve import ModelStore

        store = ModelStore(args.snapshot_dir, keep=args.snapshot_keep)
    t0 = time.time()
    fed = Federation(model.loss_fn,
                     lambda p: model.accuracy(p, xte_j, yte_j),
                     cfg, strategy=strategy)
    # --ckpt-dir without --ckpt-every still checkpoints (round 0 + final);
    # Federation.run rejects a ckpt_dir that would never be written to
    ckpt_every = args.ckpt_every
    if args.ckpt_dir is not None and ckpt_every is None and not args.resume:
        ckpt_every = args.rounds
    # Streaming telemetry: --metrics-out writes the per-round ledger as
    # JSONL live; --trace-out additionally collects it in memory for the
    # simulated-time Perfetto export after the run.
    from repro import obs

    sinks, mem = [], None
    if args.metrics_out:
        sinks.append(obs.make_sink("jsonl", path=args.metrics_out))
    if args.trace_out:
        mem = obs.InMemorySink()
        sinks.append(mem)
    sink = obs.tee(sinks)
    if args.metrics_every is not None and sink is None:
        raise SystemExit("--metrics-every requires --metrics-out or "
                         "--trace-out")
    with _profiler(args.profile_dir):
        _, hist = fed.run(
            params, cd, jax.random.key(args.seed + 1),
            snapshot_every=(args.snapshot_every if store is not None
                            else None),
            store=store, ckpt_every=ckpt_every, ckpt_dir=args.ckpt_dir,
            resume=args.resume, metrics_every=args.metrics_every, sink=sink)
    if sink is not None:
        sink.close()
    out = {"mode": "fl", "method": args.method, "engine": args.engine,
           "model": args.model, "sketch": args.sketch,
           "regime": args.regime,
           "scenario": args.scenario, "rho": args.rho,
           "scenario_spearman": round(scn.metadata["spearman"], 4),
           "source": source, "rounds": hist.rounds,
           "strategy_extras": {k: (v.tolist() if hasattr(v, "tolist") else v)
                               for k, v in extras.items()},
           "test_acc": hist.test_acc, "train_loss": hist.train_loss,
           "final_assignment": hist.assignments[-1],
           "final_counts": hist.counts[-1],
           # coalition-dynamics summaries (repro.obs.metrics; per-round
           # series are in the --metrics-out ledger / History)
           "mean_churn": round(float(np.mean(hist.churn)), 4),
           "final_entropy": round(hist.entropy[-1], 4),
           "mean_drift": round(float(np.mean(hist.drift)), 6),
           "wall_s": round(time.time() - t0, 1)}
    if fed.mesh is not None:
        from repro.launch import mesh as mesh_lib

        out["mesh"] = mesh_lib.mesh_spec(fed.mesh)
        out["backend_sharded"] = getattr(
            getattr(fed.strategy, "backend", None), "name", None)
    if args.fleet_size is not None:
        out["fleet_size"] = args.fleet_size
        out["cohort_size"] = args.clients
    if args.metrics_out:
        out["metrics_out"] = args.metrics_out
    if args.profile_dir:
        out["profile_dir"] = args.profile_dir
    if args.trace_out:
        from repro.obs import timeline

        try:
            trace = timeline.write_trace(args.trace_out, mem.records)
        except ValueError as e:
            raise SystemExit(f"--trace-out: {e}") from None
        out["trace_out"] = args.trace_out
        out["trace_events"] = len(trace["traceEvents"])
    if store is not None:
        out["snapshot_dir"] = args.snapshot_dir
        out["published_rounds"] = store.rounds()
    if args.ckpt_dir is not None:
        from repro import checkpoint

        out["ckpt_dir"] = args.ckpt_dir
        out["ckpt_rounds"] = checkpoint.available_steps(args.ckpt_dir)
        out["resumed"] = bool(args.resume)
    if hist.sim_times is not None:      # the IoT-substrate accounting
        out.update({
            "fleet": args.fleet,
            "sim_time_s": round(sum(hist.sim_times), 3),
            "wan_MB": round(sum(hist.wan_bytes) / 1e6, 3),
            "edge_MB": round(sum(hist.edge_bytes) / 1e6, 3),
            "mean_participation": round(
                float(np.mean(hist.participation)), 3)})
    if hist.quarantine is not None:     # the byzantine-attack block
        out.update({
            "attack": args.attack,
            "adv_frac": args.adv_frac,
            "rho_adv": args.rho_adv,
            "n_adversaries": int(np.asarray(hist.adversary[-1]).sum()),
            # null = diverged run (NaN is not valid RFC 8259 JSON)
            "final_quarantine": _finite(hist.quarantine[-1], 4),
            "final_contamination": _finite(hist.contamination[-1], 6)})
    if args.dp_sigma > 0.0 or np.isfinite(args.dp_clip):   # the DP block
        from repro.obs import privacy

        eps = privacy.gaussian_epsilon(args.dp_sigma, args.rounds)
        out.update({
            "dp_sigma": args.dp_sigma,
            # null = unconstrained (inf is not valid RFC 8259 JSON)
            "dp_clip": args.dp_clip if np.isfinite(args.dp_clip) else None,
            "dp_epsilon": round(eps, 4) if np.isfinite(eps) else None})
    if hist.event_times is not None:    # the event_driven energy ledger
        dead = np.asarray(hist.energy_exhausted)
        out.update({
            # null = unconstrained (inf is not valid RFC 8259 JSON)
            "energy_budget_j": (args.energy_budget
                                if np.isfinite(args.energy_budget) else None),
            "events": len(hist.event_times),
            "final_sim_time_s": round(hist.event_times[-1], 3),
            "energy_spent_j": round(
                float(np.sum(np.asarray(hist.energy_spent)[-1])), 3),
            "devices_exhausted": int(dead[-1].sum())})
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("rounds",)}, indent=1, default=float))
    return out


def run_pretrain(args) -> dict:
    from repro.configs import get, reduced
    from repro.data import synthetic
    from repro.launch import steps as steps_mod
    from repro.models import transformer as tf

    cfg = get(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params = tf.init(jax.random.key(args.seed), cfg)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"pretraining {cfg.name}: {n_params:,} params")

    step_fn, opt = steps_mod.make_train_step(cfg, optimizer=args.optimizer,
                                             lr=args.lr, remat=False)
    opt_state = opt.init(params)
    step_jit = jax.jit(step_fn, donate_argnums=(0, 1))

    toks = synthetic.lm_tokens(args.batch_size * args.steps, args.seq_len + 1,
                               cfg.vocab, seed=args.seed)
    losses = []
    t0 = time.time()
    with _profiler(args.profile_dir):
        for i in range(args.steps):
            batch = {"tokens": jnp.asarray(
                toks[i * args.batch_size:(i + 1) * args.batch_size])}
            if cfg.modality:
                batch["modal"] = jax.random.normal(
                    jax.random.key(i), (args.batch_size, cfg.n_modal_tokens,
                                        cfg.d_modal), jnp.float32)
            params, opt_state, loss = step_jit(params, opt_state, batch)
            losses.append(float(loss))
            if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
                print(f"step {i:5d}  loss {losses[-1]:.4f}  "
                      f"({(time.time()-t0)/(i+1):.2f}s/step)")
    out = {"mode": "pretrain", "arch": cfg.name, "losses": losses,
           "loss_first": losses[0], "loss_last": losses[-1],
           "wall_s": round(time.time() - t0, 1)}
    assert losses[-1] < losses[0], "training did not reduce loss"
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    return out


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", default="fl", choices=["fl", "pretrain"])
    # fl
    ap.add_argument("--method", default="coalition",
                    choices=sorted(strategies.available_strategies()))
    ap.add_argument("--regime", default="iid",
                    choices=sorted(partition.available_regimes()))
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--coalitions", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--local-epochs", type=int, default=5)
    ap.add_argument("--n-train", type=int, default=20000)
    ap.add_argument("--n-test", type=int, default=4000)
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "dot", "pallas"])
    ap.add_argument("--model", default="cnn",
                    choices=sorted(zoo_mod.available_models()),
                    help="FL model from the repro.models.zoo registry; the "
                         "federation loop is model-agnostic (per-pytree-leaf, "
                         "native float dtypes, non-float leaves untouched)")
    ap.add_argument("--sketch", default="identity",
                    choices=sorted(sketch_mod.available_sketchers()),
                    help="coalition methods: run assignment + medoid "
                         "election on a seeded (N, S) sketch of the client "
                         "weights instead of full (N, D) distances; "
                         "'identity' is the exact path, bit-for-bit")
    ap.add_argument("--sketch-dim", type=int, default=None,
                    help="sketch dimension S (rproj/countsketch; "
                         "default 256)")
    # fl: sharded federation (repro.core.sharded + repro.sim.cohort)
    ap.add_argument("--mesh", default=None,
                    help="run the coalition fused round mesh-parallel: "
                         "'host', 'production', or explicit 'axis=N' pairs "
                         "with a 'data' axis (e.g. 'data=8'; on CPU export "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                         "first). Validated eagerly; echoed in the output "
                         "JSON")
    ap.add_argument("--fleet-size", type=int, default=None,
                    help="total fleet size N for hierarchical cohort "
                         "sampling: each round an availability-weighted "
                         "cohort of --clients devices trains, so memory and "
                         "step time stay O(cohort), independent of N "
                         "(--engine scan or python)")
    ap.add_argument("--chunk", type=int, default=None,
                    help="D-sweep tile width of the fused round's streaming "
                         "passes (coalition methods; default min(D, 65536))")
    ap.add_argument("--engine", default="scan",
                    choices=["scan", "python", "semi_async", "event_driven"],
                    help="fully-jitted lax.scan round loop, legacy host "
                         "loop, the IoT-substrate partial-participation "
                         "engine, or the continuous-time event-driven "
                         "engine with per-device energy budgets")
    # fl: per-strategy hyper-parameters (None -> the rule's default)
    ap.add_argument("--top-m", type=int, default=None,
                    help="coalition_topk: aggregate only the top_m largest "
                         "coalitions")
    ap.add_argument("--trim", type=int, default=None,
                    help="fedavg_trimmed: per-coordinate trim count")
    ap.add_argument("--client-weights", default=None,
                    help="comma-separated per-client weights (fedavg_weighted"
                         " / coalition barycenters), e.g. '1,1,2,4'")
    # fl: IoT substrate (engine=semi_async)
    ap.add_argument("--fleet", default="ideal",
                    help="fleet profile name (see repro.sim.available_fleets)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="global scale on per-device availability")
    ap.add_argument("--staleness", type=float, default=0.5,
                    help="staleness decay exponent alpha in (1+tau)^-alpha")
    ap.add_argument("--deadline", type=float, default=float("inf"),
                    help="round deadline in simulated seconds")
    ap.add_argument("--energy-budget", type=float, default=float("inf"),
                    help="per-device energy budget in joules "
                         "(engine=event_driven; each train/transmit cycle "
                         "depletes it and exhausted devices retire)")
    ap.add_argument("--max-events", type=int, default=None,
                    help="event budget of the event_driven engine "
                         "(default: rounds - 1)")
    ap.add_argument("--sim-seed", type=int, default=0,
                    help="fleet sampling seed")
    # fl: checkpointing + serving snapshots (the producer half of the
    # train/serve pair; repro.launch.serve --mode fl is the consumer)
    ap.add_argument("--ckpt-dir", default=None,
                    help="write resumable federation checkpoints here")
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="checkpoint cadence in rounds (requires "
                         "--ckpt-dir; the final round is always saved; "
                         "default with --ckpt-dir: round 0 + final only)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint under --ckpt-dir "
                         "and continue; bit-for-bit identical to an "
                         "uninterrupted run")
    ap.add_argument("--snapshot-dir", default=None,
                    help="publish serving snapshots (theta + coalition "
                         "barycenters + routing assignment) into this "
                         "ModelStore directory")
    ap.add_argument("--snapshot-every", type=int, default=1,
                    help="publish cadence in rounds (with --snapshot-dir)")
    ap.add_argument("--snapshot-keep", type=int, default=None,
                    help="retain only the newest N snapshots")
    # fl: observability (repro.obs)
    ap.add_argument("--metrics-out", default=None,
                    help="stream the per-round run ledger to this JSONL "
                         "file while training (repro.obs jsonl sink); "
                         "tail it live")
    ap.add_argument("--metrics-every", type=int, default=None,
                    help="ledger cadence in rounds (default 1; round 0 and "
                         "the final round always emit)")
    ap.add_argument("--trace-out", default=None,
                    help="write a simulated-time Chrome trace-event JSON "
                         "(open in https://ui.perfetto.dev); needs "
                         "--engine semi_async or event_driven")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the run here "
                         "(real hardware time, vs. the simulated-time "
                         "--trace-out)")
    # fl: adversarial & privacy tier (repro.sim.attacks + DP client path)
    ap.add_argument("--attack", default=None,
                    choices=sorted(sim_reg.available_attacks()),
                    help="byzantine attack applied to the compromised "
                         "fraction of clients (repro.sim.attacks); absent = "
                         "every client honest")
    ap.add_argument("--adv-frac", type=float, default=0.0,
                    help="fraction of the fleet compromised, in [0, 1); "
                         "0 with --attack traces the hooks but gates them "
                         "off (bit-for-bit the clean run)")
    ap.add_argument("--rho-adv", type=float, default=0.0,
                    help="adversary placement rank coupling in [-1, 1]: "
                         "+1 compromises the strongest devices, -1 the "
                         "weakest, 0 seeded-random")
    ap.add_argument("--dp-clip", type=float, default=float("inf"),
                    help="per-client L2 clip norm on the local update "
                         "delta (inf = no clipping)")
    ap.add_argument("--dp-sigma", type=float, default=0.0,
                    help="Gaussian noise multiplier of the DP client path "
                         "(noise std = dp_sigma * dp_clip); the composed "
                         "moments-accountant epsilon lands in the output "
                         "JSON and the run ledger")
    # fl: joint fleet+data scenarios (repro.sim.scenarios)
    ap.add_argument("--scenario", default="independent",
                    help="joint fleet+data scenario (see "
                         "repro.sim.available_scenarios): 'independent' is "
                         "today's decoupled sampling; 'correlated-skew' "
                         "hands weak devices the most label-skewed shards")
    ap.add_argument("--rho", type=float, default=0.0,
                    help="fleet-data coupling strength in [0, 1]; 0 "
                         "reproduces independent sampling bit-for-bit")
    # pretrain
    ap.add_argument("--arch", default="hymba-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--flash", action="store_true",
                    help="route attention through the Pallas flash kernel")
    # shared
    ap.add_argument("--batch-size", type=int, default=10)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    return ap


def main() -> None:
    args = build_parser().parse_args()
    compile_cache.enable()

    if args.flash:
        from repro.models.layers import set_flash_kernel

        set_flash_kernel(True)
    out = run_fl(args) if args.mode == "fl" else run_pretrain(args)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, default=float)


if __name__ == "__main__":
    main()
