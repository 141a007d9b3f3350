"""End-to-end system behaviour: the paper's federation (both aggregation
rules), sharding rules, serving driver, FL round step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coalitions
from repro.core.client import ClientConfig
from repro.core.server import FederationConfig, run_federation
from repro.data import loader, partition, synthetic
from repro.models import cnn


@pytest.fixture(scope="module")
def tiny_federation_data():
    xtr, ytr = synthetic.digits(1500, seed=0)
    xte, yte = synthetic.digits(400, seed=1)
    return xtr, ytr, jnp.asarray(xte), jnp.asarray(yte)


def _run(data, method, regime, rounds=4, seed=0):
    xtr, ytr, xte, yte = data
    idx = partition.partition(regime, ytr, 10, seed=seed)
    cd = jax.tree.map(jnp.asarray, loader.client_datasets(xtr, ytr, idx))
    cfg = FederationConfig(
        n_clients=10, n_coalitions=3, rounds=rounds, method=method,
        client=ClientConfig(epochs=1, batch_size=10, lr=0.05))
    params = cnn.init(jax.random.key(seed))
    return run_federation(params, cnn.loss_fn,
                          lambda p: cnn.accuracy(p, xte, yte),
                          cd, jax.random.key(seed + 1), cfg)


@pytest.mark.slow
@pytest.mark.parametrize("method", ["coalition", "fedavg"])
def test_federation_learns(tiny_federation_data, method):
    hist = _run(tiny_federation_data, method, "iid")
    assert hist.test_acc[-1] > 0.3            # far above 0.1 chance
    assert hist.test_acc[-1] > hist.test_acc[0]


@pytest.mark.slow
def test_coalition_structure_is_nontrivial(tiny_federation_data):
    hist = _run(tiny_federation_data, "coalition", "shard")
    counts = np.array(hist.counts[-1])
    assert counts.sum() == 10
    assert (counts > 0).sum() >= 2             # at least two live coalitions


def test_paper_cnn_shapes():
    params = cnn.init(jax.random.key(0))
    x = jnp.zeros((3, 28, 28, 1))
    assert cnn.apply(params, x).shape == (3, 10)
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    # conv1 832 + conv2 51,264 + fc1 524,800 + fc2 5,130
    assert n == 582_026


def test_fl_round_step_jits():
    """The paper's round as one SPMD program (host-scale shapes)."""
    from repro.launch.steps import make_fl_round_step

    template = cnn.init(jax.random.key(0))
    n = 8
    stacked = jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (n,) + l.shape) +
        0.01 * jax.random.normal(jax.random.key(1), (n,) + l.shape), template)
    x, y = synthetic.digits(n * 16, seed=2)
    batch = {"x": jnp.asarray(x).reshape(n, 16, 28, 28, 1),
             "y": jnp.asarray(y).reshape(n, 16)}
    state = coalitions.CoalitionState(
        center_idx=jnp.array([0, 3, 6], jnp.int32), round=jnp.int32(0))
    fl_round = make_fl_round_step(cnn.loss_fn, template, n_coalitions=3,
                                  local_steps=2)
    new_params, new_state, assignment, counts = jax.jit(fl_round)(
        stacked, batch, state)
    assert int(jnp.sum(counts)) == n
    assert all(not bool(jnp.any(jnp.isnan(l.astype(jnp.float32))))
               for l in jax.tree.leaves(new_params))
    # broadcast: every client slot holds the same new global model
    lead = jax.tree.leaves(new_params)[0]
    np.testing.assert_allclose(lead[0], lead[-1], rtol=1e-6)


def test_sharding_rules_divisibility():
    """Shard only when divisible; replicate otherwise."""
    from jax.sharding import PartitionSpec as P

    from repro.configs import ARCHS
    from repro.launch import sharding
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tf

    mesh = make_host_mesh()                    # 1 real device: axes size 1
    cfg = ARCHS["hymba-1.5b"]
    params_shape = jax.eval_shape(lambda: tf.init(jax.random.key(0), cfg))
    specs = sharding.param_specs(mesh, params_shape)
    flat = {
        "/".join(str(getattr(p, "key", p)) for p in path): s
        for path, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))[0]
    }
    # every dim it proposes to shard must divide the mesh axis (size 1 -> all ok)
    for path, spec in flat.items():
        assert isinstance(spec, P)


def test_sharded_train_step_on_host_mesh():
    """A sharded train step actually RUNS on the host mesh (1 device)."""
    from repro.configs import get, reduced
    from repro.launch import sharding, steps
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tf

    cfg = reduced(get("starcoder2-7b"))
    mesh = make_host_mesh()
    params = tf.init(jax.random.key(0), cfg)
    step, opt = steps.make_train_step(cfg, lr=0.05)
    ost = opt.init(params)
    pspecs = sharding.param_specs(mesh, params)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 16), 0,
                                          cfg.vocab)}
    with mesh:
        params = jax.device_put(params, sharding.with_named(mesh, pspecs))
        p, o, loss = jax.jit(step)(params, ost, batch)
    assert jnp.isfinite(loss)


def test_serve_generate():
    from repro.configs import get, reduced
    from repro.launch.serve import generate
    from repro.models import transformer as tf

    cfg = reduced(get("hymba-1.5b"))
    params = tf.init(jax.random.key(0), cfg)
    batch = {"tokens": jnp.asarray(
        synthetic.lm_tokens(2, 12, cfg.vocab, seed=0))}
    out, stats = generate(params, cfg, batch, max_new=4, cache_len=20)
    assert out.shape == (2, 4)
    out2, _ = generate(params, cfg, batch, max_new=4, cache_len=20)
    np.testing.assert_array_equal(out, out2)   # greedy decoding deterministic


def test_fl_round_step_shardmap_matches_gspmd():
    """shard_map'd local phase == plain vmap (the §Perf FL optimization)."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_fl_round_step

    template = cnn.init(jax.random.key(0))
    n = 4
    stacked = jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (n,) + l.shape) +
        0.01 * jax.random.normal(jax.random.key(1), (n,) + l.shape), template)
    x, y = synthetic.digits(n * 8, seed=5)
    batch = {"x": jnp.asarray(x).reshape(n, 8, 28, 28, 1),
             "y": jnp.asarray(y).reshape(n, 8)}
    state = coalitions.CoalitionState(
        center_idx=jnp.array([0, 1, 2], jnp.int32), round=jnp.int32(0))
    mesh = make_host_mesh()
    base = make_fl_round_step(cnn.loss_fn, template, n_coalitions=3,
                              local_steps=1)
    opt = make_fl_round_step(cnn.loss_fn, template, n_coalitions=3,
                             local_steps=1, backend="dot",
                             shardmap_mesh=mesh, client_axis="data")
    p1, s1, a1, c1 = jax.jit(base)(stacked, batch, state)
    with mesh:
        p2, s2, a2, c2 = jax.jit(opt)(stacked, batch, state)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    for l1, l2 in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-4, atol=1e-5)


# -- entry-point plumbing: compile cache + chip smoke ------------------------

def test_compile_cache_dir_env_or_fixed_checkout_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache lives at one fixed directory inside the checkout."""
    import os

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_compile_cache")
        assert compile_cache.enable() == compile_cache.DEFAULT_DIR == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    """No TPU (this CPU host), or no repo beside the script: a non-zero
    exit and no result line."""
    import os
    import shutil
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(root, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert ("No module named 'repro'" if alone else "no TPU") in proc.stderr
