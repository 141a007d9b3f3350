"""The paper's MNIST CNN (§IV.D).

conv1: 32@5x5 + ReLU -> maxpool 2x2/2
conv2: 64@5x5 + ReLU -> maxpool 2x2/2
fc1: 512 + ReLU
fc2: 10 (class logits)

Valid padding (PyTorch Conv2d default): 28 -> 24 -> 12 -> 8 -> 4, so the
flattened feature is 4*4*64 = 1024.  Pure-functional: ``init`` -> params
pytree, ``apply`` -> logits.

Each conv block pools before its ReLU, ``relu(maxpool(conv(x) + b))``.
Max commutes with the monotone ReLU and both forms route a window's
gradient to its first max (a window whose max is <= 0 gets none, since
``relu'(0) = 0``), so this is the paper's ``maxpool(relu(.))`` to the
bit, in value and gradient, while the ReLU and its gradient run on the
pooled map and no full-size ReLU output is written.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class CNNConfig(NamedTuple):
    c1: int = 32
    c2: int = 64
    kernel: int = 5
    fc: int = 512
    n_classes: int = 10
    in_hw: int = 28

    def n_params(self) -> int:
        """Parameter count of the :func:`init` pytree (582,026 at defaults).

        Single source of truth for comm accounting — ``benchmarks/comm_cost``
        derives the paper-CNN row from this instead of a pinned constant.
        """
        spatial = (self.in_hw - self.kernel + 1) // 2     # conv1 + pool
        spatial = (spatial - self.kernel + 1) // 2        # conv2 + pool
        flat = spatial * spatial * self.c2
        return (self.kernel * self.kernel * self.c1 + self.c1
                + self.kernel * self.kernel * self.c1 * self.c2 + self.c2
                + flat * self.fc + self.fc
                + self.fc * self.n_classes + self.n_classes)


def init(key: jax.Array, cfg: CNNConfig = CNNConfig(), dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    ksz = cfg.kernel

    def he(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * jnp.sqrt(2.0 / fan_in)).astype(dtype)

    spatial = (cfg.in_hw - ksz + 1) // 2      # after conv1+pool
    spatial = (spatial - ksz + 1) // 2        # after conv2+pool
    flat = spatial * spatial * cfg.c2
    return {
        "conv1": {"w": he(k1, (ksz, ksz, 1, cfg.c1), ksz * ksz),
                  "b": jnp.zeros((cfg.c1,), dtype)},
        "conv2": {"w": he(k2, (ksz, ksz, cfg.c1, cfg.c2), ksz * ksz * cfg.c1),
                  "b": jnp.zeros((cfg.c2,), dtype)},
        "fc1": {"w": he(k3, (flat, cfg.fc), flat),
                "b": jnp.zeros((cfg.fc,), dtype)},
        "fc2": {"w": he(k4, (cfg.fc, cfg.n_classes), cfg.fc),
                "b": jnp.zeros((cfg.n_classes,), dtype)},
    }


def _conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _maxpool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def apply(params, x: jax.Array) -> jax.Array:
    """x: (B, 28, 28, 1) -> logits (B, 10)."""
    h = jax.nn.relu(_maxpool(_conv(x, params["conv1"]["w"], params["conv1"]["b"])))
    h = jax.nn.relu(_maxpool(_conv(h, params["conv2"]["w"], params["conv2"]["b"])))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    return h @ params["fc2"]["w"] + params["fc2"]["b"]


def loss_fn(params, batch) -> jax.Array:
    """Mean softmax cross-entropy on a {'x', 'y'} batch."""
    logits = apply(params, batch["x"])
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None].astype(jnp.int32),
                               axis=1)[:, 0]
    return jnp.mean(nll)


def accuracy(params, x, y) -> jax.Array:
    return jnp.mean((jnp.argmax(apply(params, x), axis=-1) == y).astype(jnp.float32))
