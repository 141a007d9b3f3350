"""The plain references against the program's own pieces, at a tiny size on
the CPU: the CNN's forward, loss and gradient; a client's local SGD; the
paper's coalition round."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import digits, reference

from repro.core import fused
from repro.core.client import ClientConfig, client_update
from repro.models import cnn

M = {"kernel": 5, "c1": 32, "c2": 64, "fc": 512, "n_classes": 10,
     "in_hw": 28}


@pytest.fixture(scope="module")
def params():
    return reference.init_cnn(jax.random.key(3), M)


@pytest.fixture(scope="module")
def batch():
    x, y = digits.digits(40, seed=5)
    return jnp.asarray(x), jnp.asarray(y)


def _close(a, b, rtol):
    a, b = np.asarray(a), np.asarray(b)
    assert np.max(np.abs(a - b)) <= rtol * np.max(np.abs(b)), \
        np.max(np.abs(a - b)) / np.max(np.abs(b))


def test_forward_loss_and_grad_match_the_program(params, batch):
    x, y = batch
    _close(reference.forward(params, x), cnn.apply(params, x), 1e-5)
    lr, gr = jax.value_and_grad(reference.loss)(params, x, y)
    lp, gp = jax.value_and_grad(cnn.loss_fn)(params, {"x": x, "y": y})
    assert float(lr) == pytest.approx(float(lp), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gr), jax.tree.leaves(gp)):
        _close(a, b, 1e-4)


def test_client_sgd_matches_client_update(params, batch):
    x, y = batch
    key = jax.random.key(9)
    pr, lr = reference.client_sgd(params, x, y, key, epochs=2, bs=10,
                                  lr=0.01)
    pp, lp = client_update(cnn.loss_fn, params, {"x": x, "y": y}, key,
                           ClientConfig(epochs=2, batch_size=10, lr=0.01))
    assert float(lr) == pytest.approx(float(lp), rel=1e-5)
    for a, b in zip(jax.tree.leaves(pr), jax.tree.leaves(pp)):
        _close(a, b, 1e-5)


def test_half_batch_fault_changes_the_update(params, batch):
    x, y = batch
    key = jax.random.key(9)
    full, _ = reference.client_sgd(params, x, y, key, epochs=1, bs=10,
                                   lr=0.01)
    half, _ = reference.client_sgd(params, x, y, key, epochs=1, bs=10,
                                   lr=0.01, half_batch=True)
    assert not np.allclose(reference.flat(full), reference.flat(half))


@pytest.mark.parametrize("n,k", [(10, 3), (12, 4)])
def test_coalition_round_matches_the_fused_round(n, k):
    kw, kc = jax.random.split(jax.random.key(n))
    theta = jax.random.normal(kw, (1, 2000))
    # clients around one θ in k loose groups, as one local epoch spreads them
    groups = jax.random.normal(kc, (k, 2000)) * 0.05
    w = theta + groups[jnp.arange(n) % k] + 0.01 * jax.random.normal(
        jax.random.key(1), (n, 2000))
    centers = reference.initial_centers(jax.random.key(2), w, k)
    a, bary, th, new, margin, mmargin = reference.coalition_round(
        w, centers, 1e-4)
    got = fused.fused_round(w, jnp.asarray(centers), backend="xla")
    np.testing.assert_array_equal(a, np.asarray(got.assignment))
    np.testing.assert_array_equal(new, np.asarray(got.new_center_idx))
    _close(bary, got.barycenters, 1e-6)
    _close(th, got.theta, 1e-6)
    assert np.all(margin > 0) and np.all(np.isinf(margin[centers]))
    assert 0 < mmargin < np.inf
