"""Step-function builders: the jitted programs the launcher/dry-run lowers.

  make_train_step   — loss/grad/SGD(+momentum) or Adam update, remat-scanned
  make_prefill_step — prompt -> filled cache + last-position logits
  make_decode_step  — ONE new token against a seq_len KV cache
  make_fl_round_step— the PAPER'S technique as one distributed program:
                      vmapped local client steps (clients on the data axis)
                      -> (N, D) weight matrix -> coalition round -> new θ
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import pytree, strategies
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.optim import optimizers as opt_mod

PyTree = Any


def make_train_step(cfg: ModelConfig, *, optimizer: str = "sgd",
                    lr: float = 1e-3, remat: bool = True) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, loss)."""
    opt = (opt_mod.adam(lr) if optimizer == "adam"
           else opt_mod.sgd(lr, momentum=0.9))

    def loss(params, cfg, batch):
        # remat=True checkpoints each layer-scan body (per-layer boundary
        # activations only survive to the backward pass)
        return tf.loss_fn(params, cfg, batch, remat=remat)

    def train_step(params, opt_state, batch):
        l, grads = jax.value_and_grad(loss)(params, cfg, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = opt_mod.apply_updates(params, updates)
        return params, opt_state, l

    return train_step, opt


def make_prefill_step(cfg: ModelConfig) -> Callable:
    def prefill_step(params, batch, cache):
        return tf.prefill(params, cfg, batch, cache)

    return prefill_step


def make_decode_step(cfg: ModelConfig) -> Callable:
    def decode_step(params, token, cache):
        return tf.decode_step(params, cfg, token, cache)

    return decode_step


def make_fl_round_step(loss_fn: Callable, template: PyTree, *, n_coalitions: int,
                       lr: float = 0.01, local_steps: int = 1,
                       backend: str = "xla", wdtype=jnp.float32,
                       wspec=None, shardmap_mesh=None,
                       client_axis="data", strategy=None) -> Callable:
    """One federated round as a single SPMD program.

    Args:
      loss_fn: (params, batch) -> scalar for the client model.
      template: single-client param pytree (structure/template).
      strategy: optional :class:`repro.core.strategies.Strategy`; defaults to
        the paper's ``coalition`` rule built from ``n_coalitions``/``backend``.
      backend: distance computation form — 'xla' (streaming diff) or 'dot'
        (Gram form; under a (clients, D-shard) layout the distance collective
        shrinks from an all-gather of W to an all-reduce of (N, N)).
      wdtype: weight-matrix dtype (bfloat16 halves every collective byte).
      wspec: optional PartitionSpec for the (N, D) weight matrix, e.g.
        P('data', 'model') — constrains GSPMD to keep D sharded through the
        coalition step.
      shardmap_mesh: if given, the local-training phase runs under shard_map
        over ``client_axis`` — clients are independent, so per-client SGD is
        collective-free BY CONSTRUCTION (GSPMD otherwise all-gathers conv
        activations across the client axis; see EXPERIMENTS.md §Perf).

    The step takes stacked client params (N, ...) (sharded over the data
    axis), per-client batches (N, b, ...), and the coalition state; runs
    ``local_steps`` of SGD per client, builds the (N, D) weight matrix,
    executes Algorithm 1, and broadcasts θ back into every client slot.
    """

    def one_client(params, batch):
        def step(p, _):
            g = jax.grad(loss_fn)(p, batch)
            return jax.tree.map(lambda w, gg: w - lr * gg, p, g), None

        params, _ = jax.lax.scan(step, params, None, length=local_steps)
        return params

    def local_phase(client_params, client_batch):
        return jax.vmap(one_client)(client_params, client_batch)

    if shardmap_mesh is not None:
        from jax.sharding import PartitionSpec as P

        def spec0(tree):
            return jax.tree.map(
                lambda l: P(client_axis, *([None] * (l.ndim - 1))), tree)

        def local_phase(client_params, client_batch):  # noqa: F811
            in_specs = (spec0(client_params), spec0(client_batch))
            return jax.shard_map(
                lambda cp, cb: jax.vmap(one_client)(cp, cb),
                mesh=shardmap_mesh, in_specs=in_specs,
                out_specs=spec0(client_params))(client_params, client_batch)

    def fl_round(client_params, client_batch, state):
        new_params = local_phase(client_params, client_batch)
        w = pytree.client_matrix(new_params, dtype=wdtype)    # (N, D)
        if wspec is not None:
            w = jax.lax.with_sharding_constraint(w, wspec)
        strat = strategy if strategy is not None else strategies.make_strategy(
            "coalition", n_clients=w.shape[0], n_coalitions=n_coalitions,
            backend=backend)
        res = strat.round(w, state)
        theta = pytree.unflatten(res.theta, template)
        n = jax.tree.leaves(client_params)[0].shape[0]
        broadcast = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), theta)
        return broadcast, res.state, res.metrics.assignment, res.metrics.counts

    return fl_round
