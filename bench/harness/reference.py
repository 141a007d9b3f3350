"""Plain references for the benchmark's ``correct``.

Written from the paper (arXiv:2401.12356, Algorithm 1 and §IV.D) and the
run's seeding rules, in straightforward ``jax.numpy``; nothing here imports
the program or takes anything it made.  The float32 reference runs under
``jax.default_matmul_precision("highest")``; ``dtype=bfloat16`` computes
the same in bfloat16 throughout, which is the control that a sound limit
has to fail.

What a federation run computes, in order (the ``scan`` engine's rules):

* run key ``key``; round 0 splits it ``key, k0, kc = split(key, 3)``;
  round r >= 1 splits the carried key ``key, kr = split(key)``;
* the round's clients: all of them, or in cohort mode the C largest of one
  Gumbel draw per fleet device, ``fold_in(fold_in(key, 0xC040), r)``
  (uniform availability); device i holds data shard ``i mod S``;
* each client j trains from the broadcast θ with ``split(k, C)[j]``: per
  epoch a ``permutation`` of its examples, SGD ``p - lr * grad`` over
  batches in order, and reports its weights and the mean batch loss of the
  last epoch;
* round 0 picks K initial centers: the first K clients of
  ``permutation(kc, C)`` whose weights differ from those already picked;
* each round: every client joins its nearest center (centers keep their
  own coalition); barycenters are the coalitions' mean weights (an empty
  coalition keeps its center's weights); θ is the mean of the barycenters;
  each coalition's new center is its member nearest its barycenter, near
  ties (within ``tie_rtol``, relative) going to the lowest client index.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

#: the cohort sampler's fold-in tag (the run's seeding rule)
COHORT_STREAM = 0xC040
LAYERS = ("conv1", "conv2", "fc1", "fc2")


def _precision(dtype, precision: str = "highest"):
    if dtype == jnp.float32:
        return jax.default_matmul_precision(precision)
    return contextlib.nullcontext()


# -- the CNN -------------------------------------------------------------------

def init_cnn(key: jax.Array, m: dict) -> dict:
    """He-normal weights and zero biases, float32, from ``key``."""
    k = m["kernel"]
    p2 = ((m["in_hw"] - k + 1) // 2 - k + 1) // 2
    shapes = {"conv1": ((k, k, 1, m["c1"]), k * k),
              "conv2": ((k, k, m["c1"], m["c2"]), k * k * m["c1"]),
              "fc1": ((p2 * p2 * m["c2"], m["fc"]), p2 * p2 * m["c2"]),
              "fc2": ((m["fc"], m["n_classes"]), m["fc"])}
    keys = jax.random.split(key, len(LAYERS))
    out = {}
    for kk, name in zip(keys, LAYERS):
        shape, fan_in = shapes[name]
        w = jax.random.normal(kk, shape, jnp.float32) * np.sqrt(2.0 / fan_in)
        out[name] = {"w": w, "b": jnp.zeros((shape[-1],), jnp.float32)}
    return out


def _conv_relu_pool(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y = jnp.maximum(y + b, 0)
    n, h, ww, c = y.shape
    return y.reshape(n, h // 2, 2, ww // 2, 2, c).max(axis=(2, 4))


def forward(p: dict, x: jax.Array) -> jax.Array:
    """(B, 28, 28, 1) images -> (B, 10) logits."""
    h = _conv_relu_pool(x, p["conv1"]["w"], p["conv1"]["b"])
    h = _conv_relu_pool(h, p["conv2"]["w"], p["conv2"]["b"])
    h = h.reshape(h.shape[0], -1)
    h = jnp.maximum(h @ p["fc1"]["w"] + p["fc1"]["b"], 0)
    return h @ p["fc2"]["w"] + p["fc2"]["b"]


def loss(p: dict, x: jax.Array, y: jax.Array) -> jax.Array:
    """Mean softmax cross-entropy."""
    z = forward(p, x)
    zmax = jnp.max(z, axis=1, keepdims=True)
    lse = zmax[:, 0] + jnp.log(jnp.sum(jnp.exp(z - zmax), axis=1))
    return jnp.mean(lse - jnp.take_along_axis(z, y[:, None], axis=1)[:, 0])


def client_sgd(p, x, y, key, *, epochs: int, bs: int, lr: float,
               half_batch: bool = False):
    """E epochs of minibatch SGD; returns (weights, last epoch's mean loss).

    ``half_batch`` trains on the first half of every batch only: a planted
    fault for the comparison's own tests, never used by a run.
    """
    n = x.shape[0]
    steps = n // bs
    if steps * bs != n:
        raise ValueError(f"{n} examples do not split into batches of {bs}")
    use = bs // 2 if half_batch else bs
    grad = jax.value_and_grad(loss)

    def step(p, batch):
        xb, yb = batch
        lval, g = grad(p, xb[:use], yb[:use])
        return jax.tree.map(lambda a, b: a - jnp.asarray(lr, a.dtype) * b,
                            p, g), lval

    last = None
    for ekey in jax.random.split(key, epochs):
        perm = jax.random.permutation(ekey, n)
        xb = x[perm].reshape((steps, bs) + x.shape[1:])
        yb = y[perm].reshape((steps, bs))
        p, losses = jax.lax.scan(step, p, (xb, yb))
        last = jnp.mean(losses.astype(jnp.float32))
    return p, last


# -- Algorithm 1 ------------------------------------------------------------------

def flat(p: dict) -> jax.Array:
    return jnp.concatenate([p[n][k].reshape(-1) for n in LAYERS
                            for k in ("w", "b")])


def unflat(v: jax.Array, like: dict) -> dict:
    out, i = {}, 0
    for n in LAYERS:
        out[n] = {}
        for k in ("w", "b"):
            size = like[n][k].size
            out[n][k] = v[i:i + size].reshape(like[n][k].shape)
            i += size
    return out


@jax.jit
def _sq_dists(w: jax.Array, rows: jax.Array) -> jax.Array:
    """(N, K) squared distances of every row of w to every row of ``rows``,
    one row of ``rows`` at a time."""
    return jax.lax.map(lambda r: jnp.sum(jnp.square(w - r[None, :]), axis=1),
                       rows).T


@jax.jit
def _member_means(w: jax.Array, onehot: jax.Array) -> jax.Array:
    """(K, D) mean weights of each coalition's members (rows of ``onehot``
    are 0/1 memberships), one coalition at a time."""
    def one(m):
        return jnp.sum(jnp.where(m[:, None] > 0, w, 0), axis=0) / jnp.sum(m)
    return jax.lax.map(one, onehot)


def initial_centers(key, w: jax.Array, k: int) -> np.ndarray:
    perm = np.asarray(jax.random.permutation(key, w.shape[0]))
    picked = []
    for i in perm:
        if len(picked) == k:
            break
        if not picked or np.all(np.asarray(_sq_dists(
                w[jnp.asarray(picked)], w[int(i)][None, :])) > 0):
            picked.append(int(i))
    if len(picked) < k:
        picked = [int(i) for i in perm[:k]]
    return np.asarray(picked, np.int32)


def coalition_round(w: jax.Array, centers: np.ndarray, tie_rtol: float):
    """Steps II-IV of Algorithm 1 on the (N, D) weights ``w``.

    Returns (assignment, barycenters (K, D), θ, new centers, margins,
    medoid margin), where ``margins[i]`` is the relative gap between client
    i's nearest and second-nearest center's squared distance, how far its
    assignment is from a tie (infinite for the centers, which keep their
    coalition), and the medoid margin is the same gap between the nearest
    and second-nearest member of a coalition to its barycenter, the least
    over the coalitions (how far the election of step IV is from a tie)."""
    n, k = w.shape[0], len(centers)
    d2c = np.asarray(_sq_dists(w, w[jnp.asarray(centers)]), np.float64)
    a = np.argmin(d2c, axis=1).astype(np.int32)
    a[centers] = np.arange(k)
    margin = np.full(n, np.inf)
    if k > 1:
        two = np.sort(d2c, axis=1)[:, :2]
        margin = (two[:, 1] - two[:, 0]) / np.maximum(two[:, 0], 1e-30)
    margin[centers] = np.inf
    onehot = (a[None, :] == np.arange(k)[:, None]).astype(np.float32)
    empty = onehot.sum(axis=1) == 0
    onehot[empty, centers[empty]] = 1.0      # an empty coalition keeps its
    bary = _member_means(w, jnp.asarray(onehot, w.dtype))   # center's row
    theta = jnp.mean(bary, axis=0)
    med = np.asarray(_sq_dists(w, bary), np.float64)
    new = np.empty(k, np.int32)
    medoid_margin = np.inf
    for j in range(k):
        members = np.flatnonzero(a == j)
        if not len(members):
            new[j] = int(np.argmin(med[:, j]))
            continue
        d = np.sort(med[members, j])
        if len(d) > 1:
            medoid_margin = min(medoid_margin,
                                (d[1] - d[0]) / max(d[0], 1e-30))
        new[j] = int(members[med[members, j] <= d[0] * (1 + tie_rtol)][0])
    return a, bary, theta, new, margin, medoid_margin


def cohort_ids(key, r: int, fleet: int, c: int) -> jax.Array:
    g = jax.random.gumbel(jax.random.fold_in(
        jax.random.fold_in(key, COHORT_STREAM), r), (fleet,), jnp.float32)
    return jax.lax.top_k(g, c)[1]


def follow(params0: dict, data: dict, key: jax.Array, cfg: dict, rounds: int,
           *, keep: tuple[int, ...] = (), dtype=jnp.float32,
           half_batch: bool = False, frozen_theta: bool = False) -> dict:
    """The federation's first ``rounds`` rounds from ``params0`` and ``key``.

    ``data`` holds the shards, ``{"x": (S, n, 28, 28, 1), "y": (S, n)}``.
    Returns, as host arrays for the comparison, per round ``loss`` (the
    clients' mean loss), ``assignment``, ``margin`` and ``medoid_margin``
    (see :func:`coalition_round`); and for each round in ``keep``, under
    ``theta[r]`` and ``wmean[r]``, θ after the round and the clients' mean
    weights.  ``frozen_theta`` plants a fault for the comparison's own
    readings, never used by a run: every round after round 0 trains from
    θ of round 0 and leaves θ unchanged, as a scanned round that returns
    its carried θ would.
    """
    c, k = cfg["n_clients"], cfg["n_coalitions"]
    s = data["x"].shape[0]
    fleet = cfg.get("fleet_size")
    sgd = jax.jit(jax.vmap(
        lambda p, x, y, kk: client_sgd(
            p, x, y, kk, epochs=cfg["local_epochs"], bs=cfg["batch_size"],
            lr=cfg["lr"], half_batch=half_batch),
        in_axes=(None, 0, 0, 0)))
    cast = (lambda t: jax.tree.map(lambda a: a.astype(dtype), t))
    out = {"loss": [], "assignment": [], "margin": [], "medoid_margin": [],
           "theta": {}, "wmean": {}}
    theta = cast(params0)
    centers = None
    run_key = key
    with _precision(dtype):
        for r in range(rounds):
            if r == 0:
                key, kr, kc = jax.random.split(key, 3)
            else:
                key, kr = jax.random.split(key)
            ids = (jnp.arange(c) if fleet is None
                   else cohort_ids(run_key, r, fleet, c))
            x = data["x"][ids % s].astype(dtype)
            y = data["y"][ids % s]
            ws, losses = sgd(theta, x, y, jax.random.split(kr, c))
            w = jax.vmap(flat)(ws)
            if r == 0:
                centers = initial_centers(kc, w, k)
            a, _, tvec, centers, margin, mmargin = coalition_round(
                w, centers, cfg["tie_rtol"])
            if not (frozen_theta and r > 0):
                theta = unflat(tvec, theta)
            out["loss"].append(float(jnp.mean(losses)))
            out["assignment"].append(np.asarray(a))
            out["margin"].append(margin)
            out["medoid_margin"].append(mmargin)
            if r in keep:
                out["theta"][r] = jax.tree.map(
                    lambda v: np.asarray(v, np.float32), theta)
                out["wmean"][r] = jax.tree.map(
                    lambda v: np.asarray(v, np.float64),
                    unflat(jnp.mean(w.astype(jnp.float32), axis=0), theta))
    return out
