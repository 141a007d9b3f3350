"""The CNN's pool-then-ReLU stage is bitwise the old ReLU-then-pool stage.

``cnn.apply`` computes ``relu(maxpool(conv(x) + b))``.  The oracle kept here
is the formulation it replaced: ``reduce_window`` max over
``relu(conv(x) + b)``.  Max commutes with the monotone ReLU, a window whose
max is <= 0 gets a zero gradient both ways (``relu'(0) = 0``), and JAX's
max-pool gradient routes a window's gradient to its first maximal position
in row-major order either way, so values and gradients agree to the bit,
ties and signed zeros included.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import cnn

N_CLIENTS = 3


def _old_pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _old_stage(y):
    return _old_pool(jax.nn.relu(y))


def _new_stage(y):
    return jax.nn.relu(cnn._maxpool(y))


def _old_apply(params, x):
    h = _old_stage(cnn._conv(x, params["conv1"]["w"], params["conv1"]["b"]))
    h = _old_stage(cnn._conv(h, params["conv2"]["w"], params["conv2"]["b"]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    return h @ params["fc2"]["w"] + params["fc2"]["b"]


def _old_loss(params, batch):
    logp = jax.nn.log_softmax(_old_apply(params, batch["x"]))
    y = batch["y"][:, None].astype(jnp.int32)
    return jnp.mean(-jnp.take_along_axis(logp, y, axis=1)[:, 0])


def _bits(tree):
    return [np.asarray(a).view(np.uint32) for a in jax.tree.leaves(tree)]


def _assert_bitwise(a, b):
    for x, y in zip(_bits(a), _bits(b), strict=True):
        np.testing.assert_array_equal(x, y)


def _tied(key, shape):
    """Pre-activations whose 2x2 windows are, at random: flat positive,
    flat negative, flat signed zeros, integers (ties at the max), or
    distinct normals."""
    kx, kw, kz = jax.random.split(key, 3)
    x = jax.random.normal(kx, shape)
    b, h, w, c = shape[-4:]
    kind = jax.random.randint(kw, shape[:-4] + (b, h // 2, w // 2, c), 0, 5)
    kind = jnp.repeat(jnp.repeat(kind, 2, -3), 2, -2)
    zeros = jnp.where(jax.random.bernoulli(kz, 0.5, shape), 0.0, -0.0)
    x = jnp.where(kind == 1, 0.5, x)
    x = jnp.where(kind == 2, -0.5, x)
    x = jnp.where(kind == 3, zeros, x)
    return jnp.where(kind == 4, jnp.round(2 * x), x)


def _tie_counts(y):
    """Windows whose max is reached more than once, by the max's sign."""
    b, h, w, c = y.shape[-4:]
    win = y.reshape(y.shape[:-4] + (b, h // 2, 2, w // 2, 2, c))
    top = win.max(axis=(-4, -2), keepdims=True)
    tied = (win == top).sum(axis=(-4, -2)) > 1
    top = top[..., 0, :, 0, :]
    return int((tied & (top > 0)).sum()), int((tied & (top <= 0)).sum())


def _params_with_ties(key):
    """The paper CNN with biases on a quarter grid, some of them zero, so
    that flat image regions give tied windows after either convolution."""
    p = cnn.init(key)
    k1, k2 = jax.random.split(jax.random.fold_in(key, 1))
    p["conv1"]["b"] = jnp.round(2 * jax.random.normal(k1, (32,))) / 4
    p["conv2"]["b"] = jnp.round(2 * jax.random.normal(k2, (64,))) / 4
    return p


@pytest.mark.parametrize("shape", [(10, 24, 24, 32), (10, 8, 8, 64)],
                         ids=["pool1", "pool2"])
def test_pool_stage_is_bitwise_relu_then_pool(shape):
    seed = shape[-1]
    y = _tied(jax.random.key(seed), (N_CLIENTS,) + shape)
    pos, nonpos = _tie_counts(y)
    assert pos > 0 and nonpos > 0
    g = jax.random.normal(jax.random.key(seed + 1),
                          (N_CLIENTS, shape[0], shape[1] // 2, shape[2] // 2,
                           shape[3]))

    out_old, vjp_old = jax.vjp(jax.jit(jax.vmap(_old_stage)), y)
    out_new, vjp_new = jax.vjp(jax.jit(jax.vmap(_new_stage)), y)
    _assert_bitwise(out_new, out_old)
    _assert_bitwise(vjp_new(g), vjp_old(g))

    # the whole model's gradient, vmapped over clients as the local phase
    # runs it, on digits whose right half is blank (flat regions, so tied
    # windows after conv1 and conv2)
    keys = jax.random.split(jax.random.key(seed + 2), N_CLIENTS)
    params = jax.vmap(_params_with_ties)(keys)
    x = jax.random.normal(jax.random.key(seed + 3),
                          (N_CLIENTS, 10, 28, 28, 1))
    x = x.at[:, :, :, 14:].set(0.0)
    batch = {"x": x, "y": jax.random.randint(jax.random.key(seed + 4),
                                             (N_CLIENTS, 10), 0, 10)}

    def convs(p, x_):
        y1 = cnn._conv(x_, p["conv1"]["w"], p["conv1"]["b"])
        y2 = cnn._conv(_old_stage(y1), p["conv2"]["w"], p["conv2"]["b"])
        return y1, y2

    for pre in jax.vmap(convs)(params, x):
        pos, nonpos = _tie_counts(pre)
        assert pos > 0 and nonpos > 0
    grad_old = jax.jit(jax.vmap(jax.grad(_old_loss)))(params, batch)
    grad_new = jax.jit(jax.vmap(jax.grad(cnn.loss_fn)))(params, batch)
    _assert_bitwise(grad_new, grad_old)
