"""Euclidean distance between model weights (paper §III.A).

``d(ω1, ω2) = sqrt(Σ_i (ω1_i − ω2_i)^2)``

For framework-scale models D ranges from ~1.6e6 (the paper's CNN) to ~1e12
(kimi-k2), so the (N, D) weight matrix never materialises distances naively:
everything is computed as chunked partial sums over D.  The concrete
implementation is selected through the :mod:`repro.core.backends` registry:

  ``'xla'``     — exact streaming diff-form (pure jnp; CPU default)
  ``'dot'``     — Gram form, collective-efficient under GSPMD sharding
  ``'pallas'``  — TPU kernels in ``repro.kernels`` (interpret-mode on CPU)

This module registers ``'xla'`` and ``'dot'`` at import time (including their
``segment_sum`` barycenter reduction — a one-hot matmul); the public functions
below resolve whichever name (or :class:`~repro.core.backends.Backend`
instance) the caller passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import backends as bk
from repro.core import fused as fz
from repro.core import instrument


def _pairwise_sq_xla(w: jax.Array, chunk: int) -> jax.Array:
    """Chunked Σ_d (w[i,d]-w[j,d])^2 -> (N, N)."""
    instrument.count_w_pass()
    n, d = w.shape
    pad = (-d) % chunk
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    nchunks = w.shape[1] // chunk
    wc = w.reshape(n, nchunks, chunk).transpose(1, 0, 2)  # (nchunks, N, chunk)

    def body(acc, wk):
        diff = wk[:, None, :] - wk[None, :, :]
        return acc + jnp.sum(diff * diff, axis=-1), None

    acc0 = jnp.zeros((n, n), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, wc)
    return acc


def _pairwise_sq_dot(w: jax.Array) -> jax.Array:
    """Gram-matrix form: ‖wi‖² + ‖wj‖² − 2⟨wi, wj⟩.

    MXU-friendly and GSPMD-friendly: with w sharded (clients × D-shards) the
    contraction over D becomes local partial Grams + an all-reduce of the tiny
    (N, N) matrix instead of an all-gather of the full weight matrix (see
    EXPERIMENTS.md §Perf, FL round)."""
    instrument.count_w_pass()
    gram = fz.centered_gram(w.astype(jnp.float32))
    sq = jnp.diagonal(gram)
    d2 = sq[:, None] + sq[None, :] - 2.0 * gram
    n = w.shape[0]
    return jnp.maximum(d2, 0.0) * (1.0 - jnp.eye(n, dtype=jnp.float32))


def _to_points_sq_xla(w: jax.Array, points: jax.Array, chunk: int) -> jax.Array:
    instrument.count_w_pass()
    n, d = w.shape
    k = points.shape[0]
    pad = (-d) % chunk
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        points = jnp.pad(points, ((0, 0), (0, pad)))
    nchunks = w.shape[1] // chunk
    wc = w.astype(jnp.float32).reshape(n, nchunks, chunk).transpose(1, 0, 2)
    pc = points.astype(jnp.float32).reshape(k, nchunks, chunk).transpose(1, 0, 2)

    def body(acc, args):
        wk, pk = args
        diff = wk[:, None, :] - pk[None, :, :]
        return acc + jnp.sum(diff * diff, axis=-1), None

    acc, _ = jax.lax.scan(body, jnp.zeros((n, k), jnp.float32), (wc, pc))
    return acc


def _to_points_sq_dot(w: jax.Array, points: jax.Array) -> jax.Array:
    instrument.count_w_pass()
    wf, pf = w.astype(jnp.float32), points.astype(jnp.float32)
    r = jnp.mean(wf, axis=0, keepdims=True)          # see fz.centered_gram
    wf, pf = wf - r, pf - r
    cross = fz.mm(wf, pf.T)
    d2 = (jnp.sum(wf * wf, 1)[:, None] + jnp.sum(pf * pf, 1)[None, :]
          - 2.0 * cross)
    return jnp.maximum(d2, 0.0)


def _segment_sum_matmul(onehot: jax.Array, w: jax.Array) -> jax.Array:
    """(K, N) one-hot × (N, D) weights — MXU does the segment reduction."""
    instrument.count_w_pass()
    return fz.mm(onehot, w.astype(jnp.float32))


bk.register_backend(bk.Backend(
    name="xla",
    pairwise_sq_dists=lambda w, chunk=None, **kw: _pairwise_sq_xla(
        w.astype(jnp.float32), fz.resolve_chunk(chunk, w.shape[1])),
    sq_dists_to_points=lambda w, p, chunk=None, **kw: _to_points_sq_xla(
        w, p, fz.resolve_chunk(chunk, w.shape[1])),
    segment_sum=lambda onehot, w, **kw: _segment_sum_matmul(onehot, w),
    fused_round=fz.fused_round_xla,
))

bk.register_backend(bk.Backend(
    name="dot",
    pairwise_sq_dists=lambda w, **kw: _pairwise_sq_dot(w),
    sq_dists_to_points=lambda w, p, **kw: _to_points_sq_dot(w, p),
    segment_sum=lambda onehot, w, **kw: _segment_sum_matmul(onehot, w),
    fused_round=fz.fused_round_dot,
))


def pairwise_sq_dists(w: jax.Array, *, chunk: int | None = None,
                      backend: str | bk.Backend = "xla") -> jax.Array:
    """Squared pairwise Euclidean distances of client weight vectors.

    Args:
      w: (N, D) client weight matrix (rows are flattened models).
      chunk: D-chunk size hint for streaming accumulation (xla backend);
        ``None`` resolves the size-derived default
        (:func:`repro.core.fused.default_chunk`).
      backend: registry name ('xla' | 'dot' | 'pallas') or a Backend.

    Returns:
      (N, N) float32 matrix of squared distances.
    """
    return bk.get_backend(backend).pairwise_sq_dists(w, chunk=chunk)


def pairwise_dists(w: jax.Array, **kw) -> jax.Array:
    """The paper's d(ω_i, ω_j): element-wise sqrt of squared distances."""
    return jnp.sqrt(jnp.maximum(pairwise_sq_dists(w, **kw), 0.0))


def sq_dists_to_points(w: jax.Array, points: jax.Array, *,
                       chunk: int | None = None,
                       backend: str | bk.Backend = "xla") -> jax.Array:
    """(N, K) squared distances from each client row to each point row.

    Used both for assignment (points = coalition-center weights) and for the
    medoid step (points = barycenters).
    """
    return bk.get_backend(backend).sq_dists_to_points(w, points, chunk=chunk)


def dists_to_points(w: jax.Array, points: jax.Array, **kw) -> jax.Array:
    return jnp.sqrt(jnp.maximum(sq_dists_to_points(w, points, **kw), 0.0))
