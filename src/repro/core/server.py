"""Strategy-driven federation engine.

The paper's outer loop (Algorithm 1) and its FedAvg baseline are two
:mod:`repro.core.strategies` entries; this module is only the *engine* that
drives an arbitrary registered strategy:

  broadcast θ -> vmapped ClientUpdate over all clients -> (N, D) weight
  matrix -> ``strategy.round(w, state)`` -> new θ + next state + metrics

Four interchangeable engines execute that round program:

  ``'scan'``       (default) — the whole federation (all R rounds, eval
                 included) is jitted ``jax.lax.scan`` programs: zero
                 host round-trips, zero per-round dispatch overhead, and
                 the :class:`History` comes back as stacked device arrays.
  ``'python'``   — the legacy host-side loop (one jitted round per step);
                 kept for debugging and as the benchmark baseline
                 (``benchmarks/run.py`` reports scan-vs-python wall clock).
  ``'semi_async'`` — the IoT-substrate engine (:mod:`repro.sim`): runs the
                 same scanned round program over a simulated device fleet
                 with partial participation and staleness-weighted merging
                 of late updates.  Each round an availability process emits
                 a participation mask; present clients deliver fresh
                 updates, absent clients keep their last delivered update
                 buffered with a growing staleness counter, and the
                 strategy aggregates the buffer under per-client
                 participation/staleness weights (the ``mask`` argument of
                 ``Strategy.round``).  Live accounting — per-round
                 simulated wall-clock and bytes-on-the-wire — lands in the
                 :class:`Trace`.  On the ``ideal`` fleet profile (full
                 participation, zero latency) the substrate reduces to
                 exact no-ops and this engine reproduces ``scan``
                 bit-for-bit (tested in ``tests/test_sim.py``).
  ``'event_driven'`` — the continuous-time variant: no round barrier at
                 all.  Devices report whenever their own
                 download+compute+upload cycle completes; the engine pops
                 completion events off a scan-carried continuous-time
                 queue, applies each arriving update through the same
                 ``Strategy.round(w, state, mask=...)`` contract with
                 staleness measured in simulated *seconds*, and depletes a
                 per-device **energy budget** every train/transmit cycle —
                 devices that can no longer afford a cycle retire
                 (energy-censored participation).  Still jitted
                 ``lax.scan`` programs (over a fixed event budget, default
                 ``rounds - 1``); on the ``ideal`` fleet with an unbounded
                 budget every event fires the full simultaneous cohort and
                 the engine reproduces ``scan`` bit-for-bit (tested in
                 ``tests/test_event_driven.py``).

Every engine is phrased as **prologue + chunked scan**: a jitted round-0
census prologue builds the engine's scan carry, and the remaining
rounds/events run as one or more jitted ``lax.scan`` *chunk* programs over
that carry (memoized per chunk length, so a plain run compiles exactly one
chunk of length R-1 — the monolithic program of old).  Chunk boundaries are
where the host gets the carry back, which is what powers the two producer
hooks of :meth:`Federation.run`:

* ``snapshot_every=k`` + ``store`` — publish a round snapshot (global θ,
  all per-coalition barycenters, the round's assignment vector) into a
  :class:`repro.serve.ModelStore` at rounds ``r % k == 0`` plus the final
  round, while a serving front end hot-swaps them live.
* ``ckpt_every=k`` + ``ckpt_dir`` — write a ``save_federation`` checkpoint
  carrying the *full* engine carry (θ, strategy state, staleness buffers,
  energy ledger, PRNG keys) and the trace-so-far; ``resume=True`` restores
  the latest one and continues **bit-for-bit identically** to an
  uninterrupted run — scan composition is exact, the step program is
  unchanged.
* ``metrics_every=k`` + ``sink`` — stream structured per-round records
  (the :class:`Trace` row plus the coalition-dynamics block) into a
  :mod:`repro.obs` sink while the run is live; pure host-side consumption
  of scan outputs that already exist, so numerics are untouched.

Every phase of a round runs under a ``jax.named_scope`` (``fl.local_phase``,
``fl.w_build``, ``fl.coalition_round``, ``fl.eval``) and the driver's host
work under ``fl.*`` profiler spans, so a profiler trace puts each device op
and each device-idle gap down to a phase (docs/observability.md); neither
changes the program's numerics.

Two orthogonal scale axes decouple the engines from fleet size and from a
single device (see docs/architecture.md "Sharded federation"):

* **Cohort mode** (``FederationConfig.fleet_size``) — the engines never see
  the fleet.  A registered fleet of N devices (up to millions) exists only
  as the O(N) ``DeviceFleet`` availability tables; every round trains an
  availability-weighted cohort of C = ``n_clients`` devices drawn by the
  hierarchical Gumbel top-k sampler (:mod:`repro.sim.cohort`), and the
  scanned programs carry the (C, D) cohort matrix — memory and step time
  are O(C·D), independent of N.  The schedule is sampled once, eagerly,
  before the first chunk; the jitted step's only N-dependence is the (C,)
  id row it scans over.  ``fleet_size=None`` is the dense pre-cohort
  behaviour, bit-for-bit.
* **Mesh mode** (``FederationConfig.mesh``) — the coalition fused round
  ``shard_map``s over the ``data`` axis of a device mesh with D-sharded
  weight tiles and O(C²) psum collectives (:mod:`repro.core.sharded`);
  bit-for-bit equal to the dense round on a 1-device mesh.

All engines follow the identical PRNG-split discipline (the substrate
engines draw availability from a *forked* stream via ``fold_in``, leaving
the client-update chain untouched), so on a fixed seed they produce the same
per-round θ and :class:`History` whenever the substrate is idle.  Per-round
metrics (loss, accuracy, coalition structure, and — under the substrate
engines — participation/sim-clock/bytes/energy) land in a :class:`History` whose list-based
view (``.rounds``, ``.test_acc``, ...) is preserved as compatibility
properties for the benchmark harness (Figs. 2-4).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import sim as sim_mod
from repro.core import backends as bk
from repro.core import pytree, strategies
from repro.core.client import (ClientConfig, client_update, dp_enabled,
                               validate_dp)
from repro.core.strategies import RoundMetrics, RoundResult, Strategy
from repro.obs import ledger as obs_ledger
from repro.obs import metrics as obs_metrics
from repro.obs import privacy as obs_privacy

PyTree = Any


def bytes_per_param(w: jax.Array) -> int:
    """On-wire bytes per parameter for a single-dtype array.

    The comm accounting must track whatever actually crosses the wire — a
    bf16 or fp8 deployment halves/quarters the bytes, and a pinned ``4``
    would silently misreport it.  The engines themselves bill whole models
    via :func:`pytree.tree_bytes` (per-leaf dtypes; a bf16 model is not a
    flattened-f32 matrix), this helper prices one homogeneous array.
    """
    return jnp.dtype(w.dtype).itemsize


class FederationConfig(NamedTuple):
    n_clients: int = 10                # cohort width C (scan width per round)
    n_coalitions: int = 3
    rounds: int = 30
    method: str = "coalition"          # any registered strategy name
    client: ClientConfig = ClientConfig()
    backend: str = "xla"               # distance/barycenter backend name
    engine: str = "scan"               # 'scan' | 'python' | 'semi_async'
    #                                    | 'event_driven'
    sim: sim_mod.SimConfig = sim_mod.SimConfig()   # IoT substrate knobs
    #: registered fleet size N for cohort mode — every round samples an
    #: availability-weighted cohort of ``n_clients`` devices out of N
    #: (:mod:`repro.sim.cohort`), so memory and step time are O(C·D)
    #: regardless of N.  None = dense mode: the fleet *is* the cohort,
    #: bit-for-bit the pre-cohort behaviour.
    fleet_size: int | None = None
    #: device-mesh spec (:func:`repro.launch.mesh.parse_mesh` — ``"data=8"``
    #: | ``"host"`` | ``"production"``) to shard the coalition fused round
    #: over; None = single-device dense round.  Validated eagerly at
    #: construction like engine/backend/fleet.
    mesh: str | None = None
    #: registered byzantine attack name (:mod:`repro.sim.attacks`); None =
    #: every client honest (the pre-attack program, verbatim).  Hyper-
    #: parameterized attacks go through the ``attack=`` argument of
    #: :class:`Federation` (mirroring ``strategy=``).
    attack: str | None = None
    #: fraction of the fleet compromised (mask drawn once per fleet,
    #: deterministic in ``sim.seed``); 0.0 with an attack set traces the
    #: attack hooks but gates them all off — bit-for-bit the clean run.
    adv_frac: float = 0.0
    #: rank coupling of adversary placement to device capability
    #: (:func:`repro.sim.attacks.adversary_mask`): +1 = the strongest
    #: devices are compromised, -1 = the weakest, 0 = seeded-random.
    rho_adv: float = 0.0


class Trace(NamedTuple):
    """Stacked per-round device arrays for R rounds (the scan outputs).

    The core metrics — loss/accuracy, the coalition structure, and the
    coalition-*dynamics* block (:mod:`repro.obs.metrics`: membership churn
    vs. the carried previous assignment, size entropy, intra-coalition
    radius, barycenter drift) — are always present and computed inside the
    scanned round from quantities the round already materializes (no extra
    W sweep; the fused path's trace-time pass count stays 2).  The substrate
    metrics are filled by the ``semi_async``/``event_driven`` engines and
    None on the idealized engines.  Under ``event_driven`` a "round" is one
    completion *event*: ``sim_time`` holds the per-event elapsed seconds (so
    cumulative sums stay meaningful across engines) and the event-only
    fields below hold the absolute timestamp and the energy ledger.
    """

    loss: jax.Array        # (R,)   mean training loss of participating clients
    acc: jax.Array         # (R,)   test accuracy of θ^(r)
    assignment: jax.Array  # (R, N) per-client group id
    counts: jax.Array      # (R, K) group sizes / masses
    churn: jax.Array       # (R,)   fraction of clients whose group flipped
    entropy: jax.Array     # (R,)   size-histogram Shannon entropy (nats)
    radius: jax.Array      # (R, K) RMS member->barycenter distance
    drift: jax.Array       # (R, K) ‖b_k(r) − b_k(r−1)‖
    sim_time: jax.Array | None = None       # (R,) simulated seconds per round
    wan_bytes: jax.Array | None = None      # (R,) bytes over the WAN link
    edge_bytes: jax.Array | None = None     # (R,) bytes over edge links
    participation: jax.Array | None = None  # (R, N) 0/1 participation mask
    # --- event_driven only ---------------------------------------------------
    event_time: jax.Array | None = None        # (R,) absolute sim seconds
    energy_spent: jax.Array | None = None      # (R, N) cumulative joules spent
    energy_exhausted: jax.Array | None = None  # (R, N) 1 = device retired
    #                                            (cannot afford another cycle)
    # --- cohort mode only ----------------------------------------------------
    cohort: jax.Array | None = None            # (R, C) sampled device ids
    # --- attack runs only (FederationConfig.attack set) ----------------------
    adversary: jax.Array | None = None      # (R, N) 0/1 compromised-row mask
    quarantine: jax.Array | None = None     # (R,) frac. adversaries embedded
    #                                         among honest clients (0 = fully
    #                                         quarantined)
    contamination: jax.Array | None = None  # (R,) honest-barycenter
    #                                         contamination bound (0 for flat
    #                                         rules / pure coalitions)


@dataclasses.dataclass
class History:
    """Federation history as stacked arrays, with the legacy list view.

    The engine produces a :class:`Trace` of device arrays (one stacked array
    per metric — what a scanned loop naturally emits).  The list-based
    attributes of the old ``History`` (``rounds``, ``train_loss``,
    ``test_acc``, ``assignments``, ``counts``) are preserved as properties so
    existing plotting/benchmark code keeps working unchanged; the substrate
    metrics get the same treatment (``sim_times``, ``wan_bytes``,
    ``edge_bytes``, ``participation`` — None unless the ``semi_async``
    engine produced them).
    """

    trace: Trace

    @property
    def rounds(self) -> list[int]:
        return list(range(int(self.trace.loss.shape[0])))

    @property
    def train_loss(self) -> list[float]:
        return [float(x) for x in np.asarray(self.trace.loss)]

    @property
    def test_acc(self) -> list[float]:
        return [float(x) for x in np.asarray(self.trace.acc)]

    @property
    def assignments(self) -> list[list[int]]:
        return np.asarray(self.trace.assignment).astype(int).tolist()

    @property
    def counts(self) -> list[list[int]]:
        return np.asarray(self.trace.counts).astype(int).tolist()

    @property
    def churn(self) -> list[float]:
        """Per-round membership churn vs. the previous round (0.0 at r=0)."""
        return [float(x) for x in np.asarray(self.trace.churn)]

    @property
    def entropy(self) -> list[float]:
        """Per-round coalition-size entropy in nats."""
        return [float(x) for x in np.asarray(self.trace.entropy)]

    @property
    def radius(self) -> list[list[float]]:
        """Per-round per-coalition intra radius (zeros for flat rules)."""
        return np.asarray(self.trace.radius).astype(float).tolist()

    @property
    def drift(self) -> list[list[float]]:
        """Per-round per-coalition barycenter drift (zeros at r=0)."""
        return np.asarray(self.trace.drift).astype(float).tolist()

    @staticmethod
    def _float_list(arr) -> list[float] | None:
        return None if arr is None else [float(x) for x in np.asarray(arr)]

    @property
    def sim_times(self) -> list[float] | None:
        """Per-round simulated wall-clock seconds (semi_async only)."""
        return self._float_list(self.trace.sim_time)

    @property
    def wan_bytes(self) -> list[float] | None:
        return self._float_list(self.trace.wan_bytes)

    @property
    def edge_bytes(self) -> list[float] | None:
        return self._float_list(self.trace.edge_bytes)

    @property
    def participation(self) -> list[list[int]] | None:
        if self.trace.participation is None:
            return None
        return np.asarray(self.trace.participation).astype(int).tolist()

    @property
    def event_times(self) -> list[float] | None:
        """Absolute simulated timestamp of each event (event_driven only)."""
        return self._float_list(self.trace.event_time)

    @property
    def energy_spent(self) -> list[list[float]] | None:
        """Per-device cumulative joules spent, per event (event_driven only)."""
        if self.trace.energy_spent is None:
            return None
        return np.asarray(self.trace.energy_spent).astype(float).tolist()

    @property
    def energy_exhausted(self) -> list[list[int]] | None:
        """Per-device energy-censoring flags, per event (event_driven only)."""
        if self.trace.energy_exhausted is None:
            return None
        return np.asarray(self.trace.energy_exhausted).astype(int).tolist()

    @property
    def cohorts(self) -> list[list[int]] | None:
        """Per-round sampled fleet device ids (cohort-mode runs only)."""
        if self.trace.cohort is None:
            return None
        return np.asarray(self.trace.cohort).astype(int).tolist()

    @property
    def adversary(self) -> list[list[int]] | None:
        """Per-round 0/1 compromised-row mask (attack runs only)."""
        if self.trace.adversary is None:
            return None
        return np.asarray(self.trace.adversary).astype(int).tolist()

    @property
    def quarantine(self) -> list[float] | None:
        """Per-round fraction of adversaries embedded among honest clients."""
        return self._float_list(self.trace.quarantine)

    @property
    def contamination(self) -> list[float] | None:
        """Per-round honest-barycenter contamination bound."""
        return self._float_list(self.trace.contamination)


# -- engine scan carries --------------------------------------------------------
# One NamedTuple per engine: the full state a chunk boundary hands back to
# the host.  ``gp`` (the θ pytree) and ``bary`` (the (n_groups, D) per-group
# models of the round just finished) lead every carry so the snapshot
# publisher and the checkpointer can read them engine-agnostically; the
# substrate engines append their buffers/ledgers.  A checkpointed carry is
# the complete resume payload — restoring it and re-running the remaining
# chunks is bit-for-bit identical to never having stopped.


class _ScanCarry(NamedTuple):
    key: jax.Array       # client-update PRNG chain
    gp: PyTree           # θ^(r) as a model pytree
    state: PyTree        # strategy state
    bary: jax.Array      # (n_groups, D) per-group models of round r
    prev_assign: jax.Array  # (N,) int32 assignment of round r (churn basis)


class _SemiAsyncCarry(NamedTuple):
    key: jax.Array
    gp: PyTree
    state: PyTree
    bary: jax.Array
    prev_assign: jax.Array
    buf: jax.Array       # (N, D) last delivered update per client
    tau: jax.Array       # (N,) staleness counters (rounds)
    astate: Any          # availability Markov state (own PRNG stream)


class _EventCarry(NamedTuple):
    key: jax.Array
    gp: PyTree
    state: PyTree
    bary: jax.Array
    prev_assign: jax.Array
    buf: jax.Array       # (N, D) last delivered update per client
    last_t: jax.Array    # (N,) sim seconds of each row's delivery
    energy: jax.Array    # (N,) joules remaining
    spent: jax.Array     # (N,) joules spent (cumulative)
    next_t: jax.Array    # (N,) completion-event queue (+inf = retired)
    clock: jax.Array     # () absolute sim seconds
    astate: Any


def _export_prng(tree: PyTree) -> PyTree:
    """Typed PRNG-key leaves -> raw uint32 key data (npz-serialisable)."""

    def conv(l):
        if hasattr(l, "dtype") and jax.dtypes.issubdtype(l.dtype,
                                                         jax.dtypes.prng_key):
            return jax.random.key_data(l)
        return l

    return jax.tree.map(conv, tree)


def _import_indexed(indexed: dict, template: PyTree) -> PyTree:
    """Rebuild ``template``'s structure from an order-indexed leaf dict
    (the ``{'0000': leaf, ...}`` form :func:`repro.checkpoint.save_federation`
    writes), re-wrapping raw key data into typed PRNG keys."""
    leaves_t, treedef = jax.tree.flatten(template)
    names = sorted(indexed)
    if len(names) != len(leaves_t):
        raise ValueError(
            f"checkpoint carry has {len(names)} leaves but this engine's "
            f"carry has {len(leaves_t)} — wrong engine or config?")
    out = []
    for n, lt in zip(names, leaves_t):
        raw = jnp.asarray(indexed[n])
        if jax.dtypes.issubdtype(lt.dtype, jax.dtypes.prng_key):
            out.append(jax.random.wrap_key_data(
                raw.astype(jnp.uint32), impl=jax.random.key_impl(lt)))
            continue
        if tuple(raw.shape) != tuple(jnp.shape(lt)):
            raise ValueError(
                f"checkpoint carry leaf {n} has shape {tuple(raw.shape)}; "
                f"this engine expects {tuple(jnp.shape(lt))}")
        out.append(raw.astype(lt.dtype))
    return jax.tree.unflatten(treedef, out)


class Federation:
    """A federation = one strategy + one engine over a client population.

    Args:
      loss_fn: (params, batch) -> scalar training loss for one client.
      eval_fn: params -> scalar test accuracy (runs *inside* the scanned
        program, so it must be jit-compatible).
      cfg: federation configuration; ``cfg.method`` names a registered
        strategy unless an explicit ``strategy`` instance is given.
        ``cfg.engine``, ``cfg.backend``, and ``cfg.sim.fleet`` are validated
        eagerly here — a typo fails at construction with the registered
        options listed, not deep inside dispatch.
      strategy: optional pre-built :class:`Strategy` (overrides cfg.method).
      attack: optional pre-built :class:`repro.sim.Attack` (overrides
        cfg.attack — the way to set attack hyper-parameters like
        ``scale_update``'s boost).
    """

    _ENGINES = ("event_driven", "python", "scan", "semi_async")

    def __init__(self, loss_fn: Callable[[PyTree, PyTree], jax.Array],
                 eval_fn: Callable[[PyTree], jax.Array],
                 cfg: FederationConfig,
                 strategy: Strategy | None = None,
                 attack: sim_mod.Attack | None = None):
        if cfg.engine not in self._ENGINES:
            raise ValueError(
                f"unknown engine {cfg.engine!r}; registered engines: "
                f"{tuple(sorted(self._ENGINES))}")
        try:
            bk.get_backend(cfg.backend)
        except KeyError:
            raise ValueError(
                f"unknown backend {cfg.backend!r}; registered backends: "
                f"{bk.available_backends()}") from None
        if cfg.sim.fleet not in sim_mod.available_fleets():
            raise ValueError(
                f"unknown fleet profile {cfg.sim.fleet!r}; registered "
                f"profiles: {sim_mod.available_fleets()}")
        if cfg.sim.scenario not in sim_mod.available_scenarios():
            raise ValueError(
                f"unknown scenario {cfg.sim.scenario!r}; registered "
                f"scenarios: {sim_mod.available_scenarios()}")
        if not 0.0 <= cfg.sim.rho <= 1.0:           # also rejects NaN
            raise ValueError(
                f"rho={cfg.sim.rho} must be in [0, 1] (fleet-data coupling "
                f"strength; 0 = independent sampling)")
        if not cfg.sim.energy_budget >= 0:          # also rejects NaN
            raise ValueError(
                f"energy_budget={cfg.sim.energy_budget} must be >= 0 "
                f"(joules; inf = unconstrained)")
        if cfg.sim.max_events is not None and cfg.sim.max_events < 0:
            raise ValueError(
                f"max_events={cfg.sim.max_events} must be >= 0 "
                f"(None = rounds - 1)")
        if cfg.fleet_size is not None:
            if cfg.fleet_size < cfg.n_clients:
                raise ValueError(
                    f"fleet_size={cfg.fleet_size} must be >= n_clients="
                    f"{cfg.n_clients} (the cohort is sampled from the fleet)")
            if self._spec_of(cfg.engine) != "scan":
                raise ValueError(
                    f"cohort mode (fleet_size set) supports the 'scan' and "
                    f"'python' engines; {cfg.engine!r} carries dense "
                    "fleet-sized buffers (staleness/energy ledgers) that do "
                    "not cohortize")
            if cfg.sim.scenario != "independent" or cfg.sim.rho != 0.0:
                raise ValueError(
                    "cohort mode requires the 'independent' scenario with "
                    "rho=0 — coupled scenarios partition data jointly with "
                    "a dense fleet")
        # Attack / DP config is validated here, before any data loads or
        # programs trace — same eager contract as engine/backend/fleet.
        if not 0.0 <= cfg.adv_frac < 1.0:       # also rejects NaN
            raise ValueError(
                f"adv_frac={cfg.adv_frac} must be in [0, 1) (a fully "
                "compromised federation has no honest signal to aggregate)")
        if not -1.0 <= cfg.rho_adv <= 1.0:      # also rejects NaN
            raise ValueError(
                f"rho_adv={cfg.rho_adv} must be in [-1, 1] (adversary-"
                "capability rank coupling; 0 = random placement)")
        self._attack = attack
        if self._attack is None and cfg.attack is not None:
            self._attack = sim_mod.make_attack(cfg.attack)   # raises on typo
        if cfg.adv_frac > 0.0 and self._attack is None:
            raise ValueError(
                f"adv_frac={cfg.adv_frac} > 0 requires an attack "
                f"(cfg.attack or the attack= argument); available: "
                f"{sim_mod.available_attacks()}")
        validate_dp(cfg.client)
        self.loss_fn = loss_fn
        self.eval_fn = eval_fn
        self.cfg = cfg
        self.strategy = strategy if strategy is not None else \
            strategies.make_strategy(cfg.method, n_clients=cfg.n_clients,
                                     n_coalitions=cfg.n_coalitions,
                                     backend=cfg.backend)
        #: parsed jax.sharding.Mesh when cfg.mesh names one (eager — a bad
        #: spec or a too-small device count fails here, not mid-run); the
        #: coalition strategy's backend is rewrapped so its fused round
        #: shard_maps over the mesh's data axis (repro.core.sharded).  Flat
        #: rules keep their dense round — the mesh only shards W sweeps.
        self.mesh = None
        if cfg.mesh is not None:
            from repro.launch import mesh as mesh_lib   # lazy: avoid cycle
            self.mesh = mesh_lib.parse_mesh(cfg.mesh)
            if getattr(self.strategy, "backend", None) is not None:
                from repro.core import sharded
                self.strategy = dataclasses.replace(
                    self.strategy, backend=sharded.sharded_backend(
                        self.strategy.backend, self.mesh))
        #: memoized jitted chunk programs, keyed by (engine spec, length,
        #: cohort?) — a plain run compiles exactly one; a snapshot cadence
        #: adds at most one more (the remainder chunk)
        self._chunk_progs: dict[tuple[str, int, bool], Callable] = {}
        if self._attack is not None:
            # Materialize the fleet + adversary mask eagerly (host-side
            # numpy), never inside a traced round program — the scan
            # engines would otherwise sample the fleet under a tracer.
            self._adversaries  # noqa: B018 — cached-property side effect

    # -- shared round pieces -----------------------------------------------------

    @functools.cached_property
    def _adversaries(self) -> jax.Array:
        """(N,) float32 0/1 compromised-device mask over the fleet.

        Deterministic in ``(fleet, adv_frac, rho_adv, sim.seed)`` — like
        ``_fleet`` itself and *not* the run key — so the memoized chunk
        programs that close over it stay valid across runs.
        """
        mask = sim_mod.adversary_mask(self._fleet, self.cfg.adv_frac,
                                      self.cfg.rho_adv,
                                      seed=self.cfg.sim.seed)
        return jnp.asarray(mask, jnp.float32)

    def _adv_row(self, ids=None) -> jax.Array | None:
        """The round's (C,) adversary mask, or None when no attack is set.

        Dense mode uses the fleet mask directly; cohort mode gathers the
        sampled device rows (compromise follows the *device*, so the same
        fleet member is adversarial in every cohort that seats it).
        """
        if self._attack is None:
            return None
        adv = self._adversaries
        return adv if ids is None else adv[ids]

    def _attack_row(self, res: RoundResult, adv: jax.Array | None) -> dict:
        """The attack block of one round's trace row (empty when clean).

        Quarantine and contamination are O(N·K) algebra over the assignment
        and the ``med_d2`` matrix the coalition round already materialized —
        no W sweep, so the fused path's trace-time pass count stays 2.  Flat
        rules have no barycenter geometry: their contamination reports 0.0
        (their quarantine is still truthful — everyone shares group 0).
        """
        if adv is None:
            return {}
        k = self.strategy.n_groups
        q = obs_metrics.quarantine_fraction(res.metrics.assignment, adv, k)
        if res.metrics.med_d2 is not None:
            c = obs_metrics.contamination(res.metrics.med_d2,
                                          res.metrics.assignment, adv, k)
        else:
            c = jnp.float32(0.0)
        return {"adversary": adv, "quarantine": q, "contamination": c}

    def _local_phase(self, global_params, client_data, key, ids=None):
        """Broadcast + vmapped ClientUpdate -> ((C, D) weights, (C,) losses).

        ``ids`` is the round's (C,) cohort of fleet device ids (cohort mode
        only): the gather contract maps device ``i`` to data shard
        ``i mod S`` where S is ``client_data``'s leading dim, so the data
        pytree stays S-sized however large the registered fleet is.  Dense
        mode (``ids=None``) compiles the identical pre-cohort program.

        With an attack configured, the round's adversary rows poison their
        gathered batch before training and transform their reported update
        after it (:mod:`repro.sim.attacks`); both hooks gate through the 0/1
        mask with ``jnp.where``, so a zero-adversary mask leaves every bit
        of the clean round intact.  Attack noise draws from the
        ``ATTACK_STREAM`` fold of the round key — the client-update chain is
        untouched.
        """
        with jax.named_scope("fl.local_phase"):
            if ids is not None:
                client_data = jax.tree.map(lambda a: a[ids % a.shape[0]],
                                           client_data)
            adv = self._adv_row(ids)
            if adv is not None:
                client_data = self._attack.poison(client_data, adv)
            ckeys = jax.random.split(key, self.cfg.n_clients)
            new_params, losses = jax.vmap(
                lambda d, k: client_update(self.loss_fn, global_params, d, k,
                                           self.cfg.client)
            )(client_data, ckeys)
        with jax.named_scope("fl.w_build"):
            w = pytree.client_matrix(new_params)
            if adv is not None:
                akey = jax.random.fold_in(key, sim_mod.ATTACK_STREAM)
                theta = pytree.flatten(global_params)
                w = self._attack.transform(w, theta, adv, akey)
        return w, losses

    def _aggregate(self, w, state, like, mask=None):
        """``strategy.round`` over W and θ as a pytree shaped like ``like``:
        the round's coalition phase (both W passes, barycenters, θ)."""
        with jax.named_scope("fl.coalition_round"):
            res = self.strategy.round(w, state, mask=mask)
            return res, pytree.unflatten(res.theta, like)

    def _eval(self, gp) -> jax.Array:
        """The in-round eval of θ."""
        with jax.named_scope("fl.eval"):
            return self.eval_fn(gp)

    def _bary_of(self, res: RoundResult) -> jax.Array:
        """The (n_groups, D) per-group models this round produced.

        Coalition rules return their actual barycenters; flat rules (which
        serve every client the global model) get θ broadcast to each group.
        """
        if res.barycenters is not None:
            return res.barycenters
        return jnp.broadcast_to(res.theta[None, :],
                                (self.strategy.n_groups, res.theta.shape[0]))

    def _radius_of(self, metrics: RoundMetrics) -> jax.Array:
        """The strategy's intra radius, zeros when a rule reports None."""
        if metrics.radius is not None:
            return metrics.radius
        return jnp.zeros((self.strategy.n_groups,), jnp.float32)

    def _dynamics_row(self, res: RoundResult, prev_assign: jax.Array,
                      prev_bary: jax.Array, bary: jax.Array) -> dict:
        """The coalition-dynamics block of one round's trace row.

        Churn and drift compare against the carried previous round
        (``prev_assign`` / ``prev_bary``); everything here is O(N·K + K·D)
        algebra over quantities the round already produced — no W sweep.
        """
        return {
            "churn": obs_metrics.membership_churn(res.metrics.assignment,
                                                  prev_assign),
            "entropy": obs_metrics.size_entropy(res.metrics.counts),
            "radius": self._radius_of(res.metrics),
            "drift": obs_metrics.barycenter_drift(bary, prev_bary),
        }

    def _round0(self, init_params, client_data, key, ids=None):
        """Round 0: ω^0 <- ClientUpdate(θ^(0)); strategy state init from ω^0.

        Always full-participation — the bootstrap census round every engine
        shares (and which fills the substrate engines' buffers).  In cohort
        mode the census runs over cohort row 0 of the schedule.  Returns
        ``(key, gp, state, bary, w0, y0)`` where ``y0`` is the round-0 row
        of the core trace metrics.
        """
        key, k0, kc = jax.random.split(key, 3)
        w0, losses0 = self._local_phase(init_params, client_data, k0, ids)
        state = self.strategy.init_state(kc, w0)
        res, gp = self._aggregate(w0, state, init_params)
        # Round 0 has no previous round to compare against: churn and drift
        # are identically 0, entropy/radius are the census partition's own.
        y0 = {"loss": jnp.mean(losses0), "acc": self._eval(gp),
              "assignment": res.metrics.assignment,
              "counts": res.metrics.counts,
              "churn": jnp.float32(0.0),
              "entropy": obs_metrics.size_entropy(res.metrics.counts),
              "radius": self._radius_of(res.metrics),
              "drift": jnp.zeros((self.strategy.n_groups,), jnp.float32)}
        if ids is not None:
            y0["cohort"] = ids
        y0.update(self._attack_row(res, self._adv_row(ids)))
        return key, gp, res.state, self._bary_of(res), w0, y0

    @functools.cached_property
    def _round0_jit(self):
        return jax.jit(self._round0)

    @functools.cached_property
    def _fleet(self) -> sim_mod.DeviceFleet:
        """The simulated device table (sampled once; deterministic in seed).

        Sized by ``fleet_size`` in cohort mode — the only O(N) state a
        cohort run ever holds (five float32 columns), everything else in the
        engine is O(C·D).
        """
        n = self.cfg.fleet_size or self.cfg.n_clients
        return sim_mod.make_fleet(self.cfg.sim.fleet, n,
                                  seed=self.cfg.sim.seed)

    def _cohort_schedule(self, key, total: int):
        """The run's (total+1, C) cohort-id table, or None in dense mode.

        Row 0 seats the census round; row r the r-th scanned round.  Drawn
        eagerly, once, from the COHORT_STREAM fork of the run key — the
        jitted round programs never see the N-wide fleet, which is what
        keeps steady-state step time independent of N.  Deterministic in
        the key, so a checkpoint resume recomputes the identical schedule
        (nothing N-sized is ever serialized).
        """
        if self.cfg.fleet_size is None:
            return None
        weights = sim_mod.effective_p(self._fleet, self.cfg.sim.participation)
        n_pos = int(jnp.sum(weights > 0))
        if n_pos < self.cfg.n_clients:
            raise ValueError(
                f"fleet has only {n_pos} devices with positive effective "
                f"availability; cannot seat a cohort of {self.cfg.n_clients}")
        ckey = jax.random.fold_in(key, sim_mod.COHORT_STREAM)
        return sim_mod.sample_cohorts(ckey, weights, total + 1,
                                      self.cfg.n_clients)

    # -- engine prologues (round 0 -> initial chunk carry) -------------------------
    # Jitted census round (memoized `_round0_jit`, which owns the user's
    # ``init_params`` and never donates them) plus eager one-off substrate
    # initialisation.  The returned carry is donated into the first chunk.

    def _prologue_scan(self, init_params, client_data, key, ids=None):
        key, gp, state, bary, _, y0 = self._round0_jit(
            init_params, client_data, key, ids)
        return _ScanCarry(key, gp, state, bary, y0["assignment"]), y0

    def _prologue_semi_async(self, init_params, client_data, key, ids=None):
        # Fork the availability stream off the run key WITHOUT consuming
        # it, so the client-update key chain is identical to 'scan'.
        assert ids is None    # cohort mode rejects this engine eagerly
        scfg = self.cfg.sim
        akey = jax.random.fold_in(key, sim_mod.AVAILABILITY_STREAM)
        key, gp, state, bary, w0, y0 = self._round0_jit(
            init_params, client_data, key)
        model_bytes = pytree.tree_bytes(gp)
        dev_time = sim_mod.device_round_time(self._fleet, model_bytes,
                                             scfg.local_work)
        astate = sim_mod.init_availability(akey, self._fleet,
                                           scfg.participation)
        mask0 = jnp.ones((self.cfg.n_clients,), bool)    # bootstrap census
        t0, wan0, edge0 = sim_mod.round_stats(
            mask0, dev_time, model_bytes, self.strategy.n_groups,
            self.strategy.hierarchical)
        y0 = dict(y0, sim_time=t0, wan_bytes=wan0, edge_bytes=edge0,
                  participation=mask0.astype(jnp.float32))
        tau0 = jnp.zeros((self.cfg.n_clients,), jnp.int32)
        return _SemiAsyncCarry(key, gp, state, bary, y0["assignment"], w0,
                               tau0, astate), y0

    def _prologue_event_driven(self, init_params, client_data, key, ids=None):
        assert ids is None    # cohort mode rejects this engine eagerly
        scfg, n = self.cfg.sim, self.cfg.n_clients
        akey = jax.random.fold_in(key, sim_mod.AVAILABILITY_STREAM)
        key, gp, state, bary, w0, y0 = self._round0_jit(
            init_params, client_data, key)
        model_bytes = pytree.tree_bytes(gp)
        dev_time = sim_mod.device_round_time(self._fleet, model_bytes,
                                             scfg.local_work)
        e_event = sim_mod.device_event_energy(self._fleet, model_bytes,
                                              scfg.local_work)
        astate = sim_mod.init_availability(akey, self._fleet,
                                           scfg.participation)
        mask0 = jnp.ones((n,), bool)                     # bootstrap census
        t0, wan0, edge0 = sim_mod.round_stats(
            mask0, dev_time, model_bytes, self.strategy.n_groups,
            self.strategy.hierarchical)
        # The census barrier closes when its straggler reports (t0).
        # The bootstrap census is forced (it fills the buffer every
        # engine shares), so a device pays for it only up to what it
        # has: the ledger can never overdraw the configured budget, and
        # a device that could not afford the full cycle starts retired
        # (energy_exhausted from row 0).  Only devices that can afford
        # the NEXT full cycle enter the event queue.
        paid0 = jnp.minimum(e_event, jnp.float32(scfg.energy_budget))
        energy0 = jnp.full((n,), scfg.energy_budget, jnp.float32) - paid0
        spent0 = paid0
        alive0 = energy0 >= e_event
        next_t0 = jnp.where(alive0, t0 + dev_time, jnp.inf)
        last_t0 = jnp.full((n,), t0)
        y0 = dict(y0, sim_time=t0, wan_bytes=wan0, edge_bytes=edge0,
                  participation=mask0.astype(jnp.float32), event_time=t0,
                  energy_spent=spent0,
                  energy_exhausted=jnp.logical_not(alive0).astype(
                      jnp.float32))
        return _EventCarry(key, gp, state, bary, y0["assignment"], w0,
                           last_t0, energy0, spent0, next_t0, t0, astate), y0

    # -- engine step programs (one scanned round / event) --------------------------

    def _step_scan(self, data):
        def step(carry: _ScanCarry, ids):
            # ``ids`` is the scanned-over cohort row in cohort mode, None
            # (no xs) on the dense path — where this step traces to exactly
            # the pre-cohort program.
            key, kr = jax.random.split(carry.key)
            w, losses = self._local_phase(carry.gp, data, kr, ids)
            res, gp = self._aggregate(w, carry.state, carry.gp)
            acc = self._eval(gp)
            bary = self._bary_of(res)
            y = {"loss": jnp.mean(losses), "acc": acc,
                 "assignment": res.metrics.assignment,
                 "counts": res.metrics.counts,
                 **self._dynamics_row(res, carry.prev_assign, carry.bary,
                                      bary)}
            if ids is not None:
                y["cohort"] = ids
            y.update(self._attack_row(res, self._adv_row(ids)))
            return _ScanCarry(key, gp, res.state, bary,
                              res.metrics.assignment), y

        return step

    def _step_semi_async(self, data):
        """Partial-participation round with staleness-weighted merging.

        Per round:

          mask  <- availability ∧ (device round time <= deadline)
          buf   <- fresh updates where present, else kept
          tau   <- 0 where present, else tau + 1
          θ     <- strategy.round(buf, state, mask=(1 + tau)^-alpha)

        plus live clock/bytes accounting from :mod:`repro.sim.clock`.
        """
        cfg, scfg = self.cfg, self.cfg.sim
        fleet, strategy = self._fleet, self.strategy

        def step(carry: _SemiAsyncCarry, _):
            key, kr = jax.random.split(carry.key)    # same chain as 'scan'
            model_bytes = pytree.tree_bytes(carry.gp)
            dev_time = sim_mod.device_round_time(fleet, model_bytes,
                                                 scfg.local_work)
            mask, astate = sim_mod.sample_mask(
                carry.astate, fleet, scfg.participation,
                device_time=dev_time, deadline=scfg.deadline)
            w, losses = self._local_phase(carry.gp, data, kr)
            buf = jnp.where(mask[:, None], w, carry.buf)
            tau = jnp.where(mask, 0, carry.tau + 1)
            # tau == 0 (just delivered) decays to exactly 1.0, so under
            # full participation eff is all-ones and the masked round is
            # bit-identical to the synchronous one.
            eff = sim_mod.staleness_weights(tau, scfg.staleness_alpha)
            res, gp = self._aggregate(buf, carry.state, carry.gp, mask=eff)
            acc = self._eval(gp)
            # Participants' mean loss, phrased through the same jnp.mean
            # as the idealized engines (scale is exactly 1.0 at full
            # participation => bit-identical codegen).
            m = mask.astype(jnp.float32)
            scale = cfg.n_clients / jnp.maximum(jnp.sum(m), 1.0)
            loss = jnp.mean(losses * (m * scale))
            sim_t, wan, edge = sim_mod.round_stats(
                mask, dev_time, model_bytes,
                strategy.n_groups, strategy.hierarchical,
                deadline=scfg.deadline)
            bary = self._bary_of(res)
            y = {"loss": loss, "acc": acc,
                 "assignment": res.metrics.assignment,
                 "counts": res.metrics.counts,
                 **self._dynamics_row(res, carry.prev_assign, carry.bary,
                                      bary),
                 "sim_time": sim_t, "wan_bytes": wan, "edge_bytes": edge,
                 "participation": m}
            y.update(self._attack_row(res, self._adv_row()))
            return _SemiAsyncCarry(key, gp, res.state, bary,
                                   res.metrics.assignment, buf, tau,
                                   astate), y

        return step

    def _step_event_driven(self, data):
        """One continuous-time completion event with the energy ledger.

        Per event:

          cohort  <- { i : next_t[i] == min(next_t) }         (time := that)
          deliver <- cohort ∧ availability draw at the report instant
          buf     <- fresh updates where delivered, else kept
          θ       <- strategy.round(buf, state, mask=(1 + age_s)^-alpha)
          energy  <- energy - cohort * event_energy; retire if < event_energy
          next_t  <- t + cycle time for survivors, +inf for retirees

        with staleness measured in simulated *seconds* since each buffered
        row was delivered.  If every device has retired, ``min(next_t)`` is
        +inf: nothing fires, the clock freezes, and the remaining events are
        recorded as zero-participation intervals (θ re-aggregates the frozen
        buffer — stable, never NaN).  Energy is charged per *attempt*
        (the device trained and transmitted even if its uplink draw failed),
        and the forced round-0 census is pre-paid in the prologue.
        """
        cfg, scfg = self.cfg, self.cfg.sim
        fleet, strategy = self._fleet, self.strategy

        def step(carry: _EventCarry, _):
            key, kr = jax.random.split(carry.key)    # same chain as 'scan'
            online, astate = sim_mod.sample_mask(carry.astate, fleet,
                                                 scfg.participation)
            model_bytes = pytree.tree_bytes(carry.gp)
            dev_time = sim_mod.device_round_time(fleet, model_bytes,
                                                 scfg.local_work)
            e_event = sim_mod.device_event_energy(fleet, model_bytes,
                                                  scfg.local_work)
            # pop the next completion cohort off the continuous-time
            # queue; an all-inf queue (every device retired) fires
            # nothing and freezes the clock.
            t_next = jnp.min(carry.next_t)
            fired_any = jnp.isfinite(t_next)
            t_now = jnp.where(fired_any, t_next, carry.clock)
            fire = jnp.logical_and(carry.next_t == t_next, fired_any)
            deliver = jnp.logical_and(fire, online)
            w, losses = self._local_phase(carry.gp, data, kr)
            buf = jnp.where(deliver[:, None], w, carry.buf)
            last_t = jnp.where(deliver, t_now, carry.last_t)
            # staleness age in simulated seconds; a row delivered this
            # event has age exactly 0 => weight exactly 1.0, so the
            # all-simultaneous cohort reduces to the synchronous round.
            eff = sim_mod.staleness_weights(t_now - last_t,
                                            scfg.staleness_alpha)
            res, gp = self._aggregate(buf, carry.state, carry.gp, mask=eff)
            acc = self._eval(gp)
            m = deliver.astype(jnp.float32)
            scale = cfg.n_clients / jnp.maximum(jnp.sum(m), 1.0)
            loss = jnp.mean(losses * (m * scale))
            paid = fire.astype(jnp.float32) * e_event
            energy = carry.energy - paid
            spent = carry.spent + paid
            alive = energy >= e_event
            next_t = jnp.where(
                fire, jnp.where(alive, t_now + dev_time, jnp.inf),
                carry.next_t)
            _, wan, edge = sim_mod.round_stats(
                deliver, dev_time, model_bytes,
                strategy.n_groups, strategy.hierarchical)
            bary = self._bary_of(res)
            y = {"loss": loss, "acc": acc,
                 "assignment": res.metrics.assignment,
                 "counts": res.metrics.counts,
                 **self._dynamics_row(res, carry.prev_assign, carry.bary,
                                      bary),
                 "sim_time": t_now - carry.clock, "wan_bytes": wan,
                 "edge_bytes": edge, "participation": m,
                 "event_time": t_now, "energy_spent": spent,
                 "energy_exhausted": jnp.logical_not(alive).astype(
                     jnp.float32)}
            y.update(self._attack_row(res, self._adv_row()))
            return _EventCarry(key, gp, res.state, bary,
                               res.metrics.assignment, buf, last_t, energy,
                               spent, next_t, t_now, astate), y

        return step

    # -- the chunked driver ----------------------------------------------------------

    @staticmethod
    def _spec_of(name: str) -> str:
        """'python' shares the scan step/carry; it just chunks per round."""
        return "scan" if name == "python" else name

    def _chunk_program(self, name: str, length: int, cohort: bool = False):
        """Jitted ``(carry, data) -> (carry', ys)`` running ``length`` rounds.

        Donation contract: the carry — the θ pytree, strategy state, the
        (n_groups, D) barycenters, and (substrate engines) the (N, D)
        buffer + staleness/energy ledgers — is produced by the prologue (or
        the previous chunk), consumed exactly once here, and returned as an
        output, so XLA updates the carried θ and the federation buffers in
        place instead of double-buffering D-sized arrays.  User-facing
        inputs (``client_data``) are never donated.
        """
        spec = self._spec_of(name)
        memo_key = (spec, length, cohort)
        if memo_key not in self._chunk_progs:
            step_builder = getattr(self, f"_step_{spec}")

            if cohort:
                # the chunk scans over its (length, C) slice of the cohort
                # schedule — the only per-round input besides the carry
                def chunk(carry, data, ids):
                    return jax.lax.scan(step_builder(data), carry, ids,
                                        length=length)
            else:
                def chunk(carry, data):
                    return jax.lax.scan(step_builder(data), carry, None,
                                        length=length)

            self._chunk_progs[memo_key] = jax.jit(chunk, donate_argnums=(0,))
        return self._chunk_progs[memo_key]

    def _n_steps(self, name: str) -> int:
        """Scan steps after the round-0 census (events for event_driven)."""
        if name == "event_driven" and self.cfg.sim.max_events is not None:
            return self.cfg.sim.max_events
        return self.cfg.rounds - 1

    @staticmethod
    def _fires(r: int, every: int | None, total: int) -> bool:
        """Hook cadence: every ``every`` rounds from round 0, plus the final
        round (the serve/resume consumer must always see the finished run)."""
        return every is not None and (r % every == 0 or r == total)

    def _publish(self, store, name: str, round_: int, carry, row) -> None:
        store.publish(round_, carry.gp, carry.bary,
                      assignment=row["assignment"], counts=row["counts"],
                      extra_meta={"engine": name, "method": self.cfg.method,
                                  "n_clients": self.cfg.n_clients})

    # -- streaming run ledger ------------------------------------------------------

    def _run_meta_record(self, name: str, carry) -> dict:
        """The ledger's ``run_meta`` header (first record of every run).

        On the substrate engines it carries the per-device cycle seconds —
        what :mod:`repro.obs.timeline` uses to draw device busy spans.
        """
        cfg = self.cfg
        rec = {"schema": obs_ledger.OBS_SCHEMA, "kind": obs_ledger.RUN_META,
               "engine": name, "method": cfg.method,
               "n_clients": cfg.n_clients,
               "n_groups": self.strategy.n_groups,
               "steps": self._n_steps(name) + 1}
        if cfg.fleet_size is not None:
            rec["fleet_size"] = cfg.fleet_size
        if self._attack is not None:
            rec.update(
                attack=self._attack.name, attack_params=self._attack.params,
                adv_frac=cfg.adv_frac, rho_adv=cfg.rho_adv,
                n_adversaries=int(np.asarray(self._adversaries).sum()))
        if dp_enabled(cfg.client):
            eps = obs_privacy.gaussian_epsilon(cfg.client.dp_sigma,
                                               self._n_steps(name) + 1)
            rec.update(
                dp_sigma=cfg.client.dp_sigma,
                # null = unconstrained (inf is not valid RFC 8259 JSON)
                dp_clip=(cfg.client.dp_clip
                         if math.isfinite(cfg.client.dp_clip) else None),
                dp_epsilon=eps if math.isfinite(eps) else None)
        if hasattr(carry, "buf"):
            model_bytes = pytree.tree_bytes(carry.gp)
            rec.update(
                fleet=cfg.sim.fleet, scenario=cfg.sim.scenario,
                model_bytes=int(model_bytes),
                device_time_s=sim_mod.device_round_time(
                    self._fleet, model_bytes, cfg.sim.local_work))
        return rec

    def _emit_rows(self, sink, part, r_start: int, metrics_every: int,
                   total: int) -> None:
        """Emit one ``round`` record per trace row the cadence selects.

        ``part`` is a stacked y-dict fresh off a chunk (or the prologue /
        a restored trace) whose row ``i`` is round ``r_start + i``.  Runs
        strictly between jitted chunks on the host — the scanned program
        never sees the sink.
        """
        rows = int(np.shape(jax.tree.leaves(part)[0])[0])
        for i in range(rows):
            r = r_start + i
            if not self._fires(r, metrics_every, total):
                continue
            rec = {"schema": obs_ledger.OBS_SCHEMA, "kind": obs_ledger.ROUND,
                   "round": r}
            rec.update({k: v[i] for k, v in part.items()})
            sink.emit(rec)

    def _save_ckpt(self, ckpt_dir: str, name: str, round_: int, carry,
                   parts: list) -> None:
        from repro import checkpoint

        trace = jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)
        checkpoint.save_federation(
            ckpt_dir, round_, carry.gp, carry.state,
            carry=_export_prng(carry), trace=trace,
            extra_meta={"engine": name, "method": self.cfg.method,
                        "rounds": self.cfg.rounds})

    def _restore_ckpt(self, ckpt_dir: str, name: str, carry_template,
                      y_keys) -> tuple[int, Any, list] | None:
        """Latest-checkpoint restore: ``(rounds done, carry, trace parts)``.

        Returns None when the directory holds no checkpoint yet (a resume
        flag on a first run is then just a fresh start).
        """
        from repro import checkpoint

        step = checkpoint.latest_step(ckpt_dir)
        if step is None:
            return None
        tree, meta = checkpoint.load(ckpt_dir, step)
        if meta.get("schema") != checkpoint.FEDERATION_SCHEMA:
            raise ValueError(
                f"{ckpt_dir} step {step} is not a federation checkpoint "
                f"(schema={meta.get('schema')!r})")
        if meta.get("engine") != name:
            raise ValueError(
                f"checkpoint at {ckpt_dir} was written by engine "
                f"{meta.get('engine')!r}; cannot resume with {name!r}")
        if "carry" not in tree or "trace" not in tree:
            raise ValueError(
                f"checkpoint at {ckpt_dir} step {step} has no resume "
                f"payload (published snapshot instead of ckpt_every?)")
        if set(tree["trace"]) != set(y_keys):
            raise ValueError(
                f"checkpoint trace metrics {sorted(tree['trace'])} do not "
                f"match engine {name!r} metrics {sorted(y_keys)}")
        carry = _import_indexed(tree["carry"], carry_template)
        parts = [jax.tree.map(jnp.asarray, tree["trace"])]
        return int(step), carry, parts

    def _run_driver(self, name, init_params, client_data, key, *,
                    snapshot_every=None, store=None,
                    ckpt_every=None, ckpt_dir=None, resume=False,
                    metrics_every=None, sink=None):
        # Host spans (``fl.*``, the profiler's TraceMe: one inactive check
        # each when no profiler runs) name what the host does between the
        # device programs, so a trace can put each device-idle gap down to it.
        total = self._n_steps(name)
        with TraceAnnotation("fl.cohort_schedule"):
            cohorts = self._cohort_schedule(key, total)
        with TraceAnnotation("fl.prologue"):
            carry, y0 = getattr(self, f"_prologue_{self._spec_of(name)}")(
                init_params, client_data, key,
                None if cohorts is None else cohorts[0])
        parts = [jax.tree.map(lambda a: jnp.asarray(a)[None], y0)]
        r_done = 0
        restored = (self._restore_ckpt(ckpt_dir, name, carry, y0)
                    if resume else None)
        if restored is not None:
            r_done, carry, parts = restored
        else:
            # round-0 hooks (cadence fires at r=0: a consumer can start
            # serving the census model immediately)
            if self._fires(0, snapshot_every, total):
                with TraceAnnotation("fl.publish"):
                    self._publish(store, name, 0, carry, y0)
            if self._fires(0, ckpt_every, total):
                with TraceAnnotation("fl.checkpoint"):
                    self._save_ckpt(ckpt_dir, name, 0, carry, parts)
        if sink is not None:
            with TraceAnnotation("fl.emit"):
                sink.emit(self._run_meta_record(name, carry))
                # covers round 0 on a fresh start; on resume the restored
                # trace is re-emitted so the ledger is complete from round 0
                # whichever checkpoint the run picked up at
                self._emit_rows(sink, parts[0], 0, metrics_every, total)

        if name == "python":
            boundaries = list(range(r_done + 1, total + 1))
        else:
            boundaries = sorted(
                r for r in range(r_done + 1, total + 1)
                if r == total or self._fires(r, snapshot_every, total)
                or self._fires(r, ckpt_every, total)
                or self._fires(r, metrics_every, total))
        for r in boundaries:
            prog = self._chunk_program(name, r - r_done,
                                       cohort=cohorts is not None)
            with TraceAnnotation("fl.dispatch"):
                if cohorts is None:
                    carry, ys = prog(carry, client_data)
                else:
                    carry, ys = prog(carry, client_data,
                                     cohorts[r_done + 1:r + 1])
            parts.append(ys)
            if sink is not None:
                with TraceAnnotation("fl.emit"):
                    self._emit_rows(sink, ys, r_done + 1, metrics_every,
                                    total)
            r_done = r
            if self._fires(r, snapshot_every, total):
                with TraceAnnotation("fl.publish"):
                    row = jax.tree.map(lambda a: a[-1], ys)
                    self._publish(store, name, r, carry, row)
            if self._fires(r, ckpt_every, total):
                with TraceAnnotation("fl.checkpoint"):
                    self._save_ckpt(ckpt_dir, name, r, carry, parts)
        with TraceAnnotation("fl.history"):
            stacked = (parts[0] if len(parts) == 1 else
                       jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts))
            trace = Trace(**stacked)
            return carry.gp, History(trace=jax.device_get(trace))

    def run(self, init_params: PyTree, client_data: PyTree, key: jax.Array,
            *, engine: str | None = None,
            snapshot_every: int | None = None, store=None,
            ckpt_every: int | None = None, ckpt_dir: str | None = None,
            resume: bool = False,
            metrics_every: int | None = None,
            sink: obs_ledger.Sink | None = None) -> tuple[PyTree, History]:
        """Run the full federation; returns (final θ pytree, History).

        Args:
          init_params: θ^(0).
          client_data: pytree of arrays with leading dim (n_clients, n_local, ...).
          key: PRNG key (same key + same strategy => same History on either
            idealized engine; also on 'semi_async' and 'event_driven' over
            the 'ideal' fleet).
          engine: override ``cfg.engine`` ('scan' | 'python' | 'semi_async'
            | 'event_driven').
          snapshot_every: publish a serving snapshot (θ + per-coalition
            barycenters + assignment) into ``store`` at every round
            ``r % snapshot_every == 0`` plus the final round.
          store: a :class:`repro.serve.ModelStore` (required with
            ``snapshot_every``).
          ckpt_every: write a resumable ``save_federation`` checkpoint into
            ``ckpt_dir`` on the same cadence rule.
          ckpt_dir: checkpoint directory (required with ``ckpt_every`` or
            ``resume``; rejected without either, since nothing would ever
            be written).
          resume: restore the latest checkpoint under ``ckpt_dir`` and
            continue — bit-for-bit identical to the uninterrupted run (the
            checkpoint carries the full engine carry; an empty directory is
            just a fresh start).
          metrics_every: stream a structured ``round`` record into ``sink``
            every ``metrics_every`` rounds (plus round 0 and the final
            round) — live telemetry at the same chunk boundaries that power
            snapshots/checkpoints, with zero effect on traced numerics.
            Requires ``sink``; a ``sink`` alone defaults to every round.
          sink: a :class:`repro.obs.Sink` (``repro.obs.make_sink``); the
            run opens with one ``run_meta`` record, then per-round records.
            The caller owns the sink's lifetime (it is not closed here).
        """
        name = engine if engine is not None else self.cfg.engine
        if name not in self._ENGINES:
            raise ValueError(f"unknown engine {name!r}; registered engines: "
                             f"{tuple(sorted(self._ENGINES))}")
        if self.cfg.fleet_size is not None and self._spec_of(name) != "scan":
            raise ValueError(
                f"cohort mode (fleet_size set) supports the 'scan' and "
                f"'python' engines, not {name!r}")
        if snapshot_every is not None:
            if snapshot_every < 1:
                raise ValueError(
                    f"snapshot_every={snapshot_every} must be >= 1")
            if store is None:
                raise ValueError("snapshot_every requires a store "
                                 "(repro.serve.ModelStore)")
        elif store is not None:
            raise ValueError("store given without snapshot_every")
        if ckpt_every is not None:
            if ckpt_every < 1:
                raise ValueError(f"ckpt_every={ckpt_every} must be >= 1")
            if ckpt_dir is None:
                raise ValueError("ckpt_every requires ckpt_dir")
        elif ckpt_dir is not None and not resume:
            raise ValueError("ckpt_dir given without ckpt_every or resume "
                             "would never write a checkpoint")
        if resume and ckpt_dir is None:
            raise ValueError("resume requires ckpt_dir")
        if metrics_every is not None:
            if metrics_every < 1:
                raise ValueError(
                    f"metrics_every={metrics_every} must be >= 1")
            if sink is None:
                raise ValueError("metrics_every requires a sink "
                                 "(repro.obs.make_sink)")
        elif sink is not None:
            metrics_every = 1                   # a sink alone: every round
        with TraceAnnotation("fl.run"):
            return self._run_driver(
                name, init_params, client_data, key,
                snapshot_every=snapshot_every, store=store,
                ckpt_every=ckpt_every, ckpt_dir=ckpt_dir, resume=resume,
                metrics_every=metrics_every, sink=sink)


def run_federation(init_params: PyTree,
                   loss_fn: Callable[[PyTree, PyTree], jax.Array],
                   eval_fn: Callable[[PyTree], jax.Array],
                   client_data: PyTree,
                   key: jax.Array,
                   cfg: FederationConfig,
                   strategy: Strategy | None = None) -> History:
    """Compatibility entry point: build a :class:`Federation` and run it.

    ``cfg.method`` resolves through the strategy registry — any registered
    aggregation rule runs through the same engine.
    """
    _, hist = Federation(loss_fn, eval_fn, cfg, strategy=strategy).run(
        init_params, client_data, key)
    return hist
