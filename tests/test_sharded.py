"""Sharded federation: mesh-parallel fused rounds + hierarchical cohorts.

Run twice in CI: once inside tier-1 (single real device — the 1-device-mesh
bit-for-bit parity tier) and once in a dedicated step with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` where the
``@need8`` tests exercise real D-sharding, padding, and psum stitching.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import coalitions, fused as fz, instrument, server, sharded
from repro.launch import mesh as mesh_lib
from repro.sim import cohort as cohort_mod

DEVS = len(jax.devices())
need8 = pytest.mark.skipif(
    DEVS < 8, reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

BACKENDS = ("xla", "dot", "pallas")
jax.config.update("jax_enable_x64", False)


def _w(n=10, d=1000, seed=0):
    return jax.random.normal(jax.random.key(seed), (n, d), jnp.float32)


# -- 1-device mesh: bit-for-bit parity with the dense round -------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_one_device_mesh_bitexact(backend):
    mesh = mesh_lib.parse_mesh("data=1")
    sb = sharded.sharded_backend(backend, mesh)
    w = _w()
    ci = jnp.array([0, 3, 7], jnp.int32)
    cw = jnp.abs(jax.random.normal(jax.random.key(1), (10,)))
    for kw in ({}, {"client_weights": cw}):
        dense = fz.fused_round(w, ci, backend=backend, **kw)
        shard = fz.fused_round(w, ci, backend=sb, **kw)
        for a, b in zip(dense, shard):
            assert jnp.array_equal(a, b), (backend, kw.keys())


def test_sharded_backend_name_and_validation():
    mesh = mesh_lib.parse_mesh("data=1")
    assert sharded.sharded_backend("xla", mesh).name == "xla@data1"
    with pytest.raises(KeyError, match="unknown backend"):
        sharded.sharded_backend("nope", mesh)
    with pytest.raises(ValueError, match="no 'model' axis|has no"):
        sharded.sharded_backend("xla", mesh, axis="model")


# -- 8-device mesh: real sharding ---------------------------------------------

def _clustered_w(d=1000):
    """16 clients in 3 well-separated clusters (5/5/6 members) — generic
    member→barycenter distances, so no exact medoid ties that per-shard
    float noise could flip either way."""
    protos = jnp.array([[-6.0], [0.0], [6.0]]) * jnp.ones((3, d))
    noise = jax.random.normal(jax.random.key(11), (16, d))
    owner = jnp.array([0] * 5 + [1] * 5 + [2] * 6)
    return protos[owner] + noise


@need8
@pytest.mark.parametrize("backend", BACKENDS)
def test_eight_device_parity(backend):
    """D=1000 is not divisible by 8 — exercises the zero-pad path too."""
    mesh = mesh_lib.parse_mesh("data=8")
    sb = sharded.sharded_backend(backend, mesh)
    w = _clustered_w()
    ci = jnp.array([0, 5, 10], jnp.int32)
    dense = fz.fused_round(w, ci, backend=backend)
    shard = fz.fused_round(w, ci, backend=sb)
    # per-shard chunking moves float-sum boundaries: allclose, not bitwise
    assert jnp.array_equal(dense.assignment, shard.assignment)
    assert jnp.array_equal(dense.counts, shard.counts)
    assert jnp.array_equal(dense.new_center_idx, shard.new_center_idx)
    np.testing.assert_allclose(dense.barycenters, shard.barycenters,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dense.theta, shard.theta, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dense.radius, shard.radius,
                               rtol=1e-3, atol=0.05)


@need8
@pytest.mark.parametrize("fused", (True, False))
def test_eight_device_run_round(fused):
    """The full Algorithm-1 round (strategy-level entry) on a sharded
    backend agrees with dense for both the fused and the composed path."""
    mesh = mesh_lib.parse_mesh("data=8")
    sb = sharded.sharded_backend("xla", mesh)
    w = _w(n=12, d=520)
    state = coalitions.init_centers(jax.random.key(2), w, 3)
    dense = coalitions.run_round(w, state, backend="xla", fused=fused)
    shard = coalitions.run_round(w, state, backend=sb, fused=fused)
    assert jnp.array_equal(dense.assignment, shard.assignment)
    assert jnp.array_equal(dense.new_center_idx, shard.new_center_idx)
    np.testing.assert_allclose(dense.theta, shard.theta, rtol=1e-5, atol=1e-5)


@need8
def test_two_pass_invariant_under_shard_map():
    """Each shard reads its W tile exactly twice (trace-time count)."""
    mesh = mesh_lib.parse_mesh("data=8")
    w = _w(n=8, d=800)
    ci = jnp.array([0, 2], jnp.int32)
    for backend in BACKENDS:
        sb = sharded.sharded_backend(backend, mesh)
        with instrument.count_w_passes() as passes:
            jax.make_jaxpr(
                lambda w_: fz.fused_round(w_, ci, backend=sb))(w)
        assert passes() == 2, backend


# -- sketched rounds under shard_map -------------------------------------------

def _sk(dim=64):
    from repro.core import sketch
    return sketch.make_sketcher("rproj", dim=dim)


@pytest.mark.parametrize("backend", BACKENDS)
def test_one_device_mesh_sketched_parity(backend):
    """Sketched round on a 1-device mesh vs the dense sketched round: same
    per-column sketch map, same assignment/medoids; floats to roundoff."""
    mesh = mesh_lib.parse_mesh("data=1")
    sb = sharded.sharded_backend(backend, mesh)
    w = _clustered_w(d=520)
    ci = jnp.array([0, 5, 10], jnp.int32)
    dense = fz.fused_round(w, ci, backend=backend, sketcher=_sk())
    shard = fz.fused_round(w, ci, backend=sb, sketcher=_sk())
    assert jnp.array_equal(dense.assignment, shard.assignment)
    assert jnp.array_equal(dense.counts, shard.counts)
    assert jnp.array_equal(dense.new_center_idx, shard.new_center_idx)
    np.testing.assert_allclose(dense.theta, shard.theta, rtol=1e-5, atol=1e-5)


@need8
@pytest.mark.parametrize("backend", BACKENDS)
def test_eight_device_sketched_parity(backend):
    """Real D-sharding (520 = 8*65: no pad; offsets exercise the
    global-column-index determinism of the sketch map)."""
    mesh = mesh_lib.parse_mesh("data=8")
    sb = sharded.sharded_backend(backend, mesh)
    w = _clustered_w(d=520)
    ci = jnp.array([0, 5, 10], jnp.int32)
    dense = fz.fused_round(w, ci, backend=backend, sketcher=_sk())
    shard = fz.fused_round(w, ci, backend=sb, sketcher=_sk())
    assert jnp.array_equal(dense.assignment, shard.assignment)
    np.testing.assert_allclose(dense.theta, shard.theta, rtol=2e-4, atol=1e-4)


@need8
def test_sketched_two_pass_invariant_under_shard_map():
    """Each shard reads its W tile exactly twice in the sketched round too:
    one partial-sketch sweep, one barycenter/theta sweep."""
    mesh = mesh_lib.parse_mesh("data=8")
    w = _w(n=8, d=800)
    ci = jnp.array([0, 2], jnp.int32)
    for backend in BACKENDS:
        sb = sharded.sharded_backend(backend, mesh)
        with instrument.count_w_passes() as passes:
            jax.make_jaxpr(lambda w_: fz.fused_round(
                w_, ci, backend=sb, sketcher=_sk()))(w)
        assert passes() == 2, backend


# -- hierarchical cohort sampling ---------------------------------------------

def test_cohort_hierarchical_matches_flat():
    """Cell-wise Gumbel top-k == flat top-k, bit for bit (associativity)."""
    key = jax.random.key(3)
    weights = jnp.abs(jax.random.normal(jax.random.key(4), (1000,))) + 0.01
    flat = cohort_mod.sample_cohort(key, weights, 32, cell_size=1 << 20)
    cells = cohort_mod.sample_cohort(key, weights, 32, cell_size=64)
    assert jnp.array_equal(flat, cells)


def test_cohort_deterministic_unique_and_weighted():
    key = jax.random.key(5)
    weights = jnp.concatenate(
        [jnp.zeros(50), jnp.ones(150)])        # first 50 devices unavailable
    ids = cohort_mod.sample_cohort(key, weights, 40)
    ids2 = cohort_mod.sample_cohort(key, weights, 40)
    assert jnp.array_equal(ids, ids2)
    assert len(np.unique(np.asarray(ids))) == 40       # without replacement
    assert int(jnp.min(ids)) >= 50                     # zero weight excluded
    sched = cohort_mod.sample_cohorts(key, weights, 5, 40)
    assert sched.shape == (5, 40) and sched.dtype == jnp.int32
    assert jnp.array_equal(sched[0], cohort_mod.sample_cohort(
        jax.random.fold_in(key, 0), weights, 40))


# -- cohort-mode federation ---------------------------------------------------

def _fed(cfg_kw, n_shards=6):
    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

    def eval_fn(p):
        return -jnp.sum(p["w"] ** 2)

    data = {"x": jax.random.normal(jax.random.key(6), (n_shards, 32, 4)),
            "y": jax.random.normal(jax.random.key(7), (n_shards, 32))}
    cfg_kw.setdefault("sim", server.sim_mod.SimConfig(fleet="lognormal-edge"))
    cfg = server.FederationConfig(
        n_clients=5, n_coalitions=2, rounds=3, **cfg_kw)
    fed = server.Federation(loss_fn, eval_fn, cfg)
    return fed, {"w": jnp.zeros((4,))}, data


def test_cohort_federation_deterministic():
    fed, init, data = _fed(dict(fleet_size=500))
    gp, hist = fed.run(init, data, jax.random.key(8))
    gp2, hist2 = _fed(dict(fleet_size=500))[0].run(init, data,
                                                  jax.random.key(8))
    assert hist.cohorts == hist2.cohorts
    assert np.asarray(hist.trace.cohort).shape == (3, 5)
    assert (np.asarray(hist.test_acc) == np.asarray(hist2.test_acc)).all()
    assert bool(jnp.all(gp["w"] == gp2["w"]))


def test_dense_federation_has_no_cohort():
    fed, init, data = _fed({}, n_shards=5)
    _, hist = fed.run(init, data, jax.random.key(8))
    assert hist.trace.cohort is None and hist.cohorts is None


def test_million_fleet_smoke():
    """N=2^20 fleet, C=5 cohort: the scan never materialises (N, D)."""
    n_fleet = 1_048_576
    fed, init, data = _fed(dict(fleet_size=n_fleet))
    gp, hist = fed.run(init, data, jax.random.key(9))
    ids = np.asarray(hist.trace.cohort)
    assert ids.shape == (3, 5)
    assert ids.min() >= 0 and ids.max() < n_fleet
    for row in ids:
        assert len(np.unique(row)) == len(row)
    gp2, _ = _fed(dict(fleet_size=n_fleet))[0].run(init, data,
                                                   jax.random.key(9))
    assert bool(jnp.all(gp["w"] == gp2["w"]))


@need8
def test_cohort_plus_mesh_federation():
    fed, init, data = _fed(dict(fleet_size=500))
    fedm, _, _ = _fed(dict(fleet_size=500, mesh="data=8"))
    assert fedm.strategy.backend.name == "xla@data8"
    gp, hist = fed.run(init, data, jax.random.key(10))
    gpm, histm = fedm.run(init, data, jax.random.key(10))
    assert hist.cohorts == histm.cohorts
    np.testing.assert_allclose(gp["w"], gpm["w"], rtol=1e-5, atol=1e-6)


# -- validation + mesh parsing ------------------------------------------------

def test_cohort_mode_validation():
    with pytest.raises(ValueError, match="fleet_size"):
        _fed(dict(fleet_size=3))
    with pytest.raises(ValueError, match="cohort mode"):
        _fed(dict(fleet_size=500, engine="semi_async"))
    with pytest.raises(ValueError, match="cohort mode"):
        _fed(dict(fleet_size=500,
                  sim=server.sim_mod.SimConfig(fleet="lognormal-edge",
                                               scenario="correlated-skew",
                                               rho=0.5)))


def test_parse_mesh_errors_mention_xla_flags():
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        mesh_lib.parse_mesh(f"data={DEVS + 1}")
    with pytest.raises(ValueError, match="data"):
        mesh_lib.parse_mesh("model=1")
    with pytest.raises(ValueError, match="duplicate|once"):
        mesh_lib.parse_mesh("data=1,data=1")
    m = mesh_lib.parse_mesh("data=1")
    assert mesh_lib.mesh_spec(m) == "data=1"


def test_production_mesh_raises_when_too_few_devices():
    """No silent fall-back: a 'production' mesh on too few devices (the
    256-device pod shape on one host, or one TPU chip) is an error that
    names the smaller meshes to use instead."""
    with pytest.raises(ValueError, match="needs 256 devices.*data=N"):
        mesh_lib.parse_mesh("production")
    with pytest.raises(ValueError, match="needs 512 devices"):
        mesh_lib.make_production_mesh(multi_pod=True)
