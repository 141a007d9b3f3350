"""Pallas TPU kernels: the two-pass fused coalition round.

Algorithm 1's server step over an (N, D) client weight matrix with tiny N/K
and framework-scale D is HBM-bandwidth-bound, so the round is organised as
exactly two streaming sweeps (see :mod:`repro.core.fused`):

  ``center_sq_dists``        — pass 1: assignment distances.  The K center
      rows are reconstructed *inside* the kernel from the resident chunk via
      a (K, N) one-hot matmul (MXU), so no (K, D) center gather ever leaves
      VMEM, and the (N, K) accumulator stays resident across the grid.

  ``fused_coalition_stats``  — pass 2: one chunk read feeds three results.
      The (K, N) aggregation matrix (client weights, empty-coalition fallback
      and barycenter denominators pre-folded by the caller) emits the
      barycenter tile ``b = m @ wk`` and its column-mean θ tile (each written
      exactly once, like ``segment_mean``), while the client→barycenter
      distances for the medoid step accumulate into a resident (N, K) block.

Both kernels take their distances in Gram form on rows *centered* per chunk
(the chunk's column mean over clients subtracted from every row and every
center or barycenter): distances are translation-invariant, and clients
that start a round from one θ sit close together far from the origin, so
uncentered ``‖w‖² + ‖c‖² − 2⟨w, c⟩`` would cancel away most of f32's digits.

Grid: (D // block_d,) for both — the only axis is a reduction for the
accumulators (constant output index_map) and a pure stream for the tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: full f32 MXU contraction (see ``repro.core.fused.mm``: the Gram form
#: cancels catastrophically at the TPU's default bf16-pass precision)
_HIGHEST = jax.lax.Precision.HIGHEST


def _center_dist_kernel(w_ref, conehot_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    wk = w_ref[...].astype(jnp.float32)              # (N, BD)
    wk = wk - jnp.mean(wk, axis=0, keepdims=True)    # centered chunk
    ck = jax.lax.dot_general(                        # (K, BD) center rows,
        conehot_ref[...].astype(jnp.float32), wk,    # gathered on the MXU
        (((1,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)
    cross = jax.lax.dot_general(                     # (N, K)
        wk, ck, (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)
    wsq = jnp.sum(wk * wk, axis=1)
    csq = jnp.sum(ck * ck, axis=1)
    out_ref[...] += wsq[:, None] + csq[None, :] - 2.0 * cross


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def center_sq_dists(w: jax.Array, conehot: jax.Array, *, block_d: int = 16384,
                    interpret: bool) -> jax.Array:
    """(N, D), (K, N) one-hot of center indices -> (N, K) squared distances.

    VMEM working set: (N + K)·block_d·4 for the chunk + centers, plus the
    (N, K) accumulator — ≈5 MB at N=64, K=8, block_d=16384.
    """
    n, d = w.shape
    k = conehot.shape[0]
    pad = (-d) % block_d
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    nchunks = w.shape[1] // block_d
    out = pl.pallas_call(
        _center_dist_kernel,
        grid=(nchunks,),
        in_specs=[pl.BlockSpec((n, block_d), lambda i: (0, i)),
                  pl.BlockSpec((k, n), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((n, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(w, conehot)
    return jnp.maximum(out, 0.0)


def _stats_kernel(m_ref, w_ref, b_ref, t_ref, d2_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        d2_ref[...] = jnp.zeros_like(d2_ref)

    wk = w_ref[...].astype(jnp.float32)              # (N, BD)
    m = m_ref[...].astype(jnp.float32)               # (K, N)
    bc = jax.lax.dot_general(                        # (K, BD) barycenter tile
        m, wk, (((1,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)
    b_ref[...] = bc
    t_ref[...] = jnp.mean(bc, axis=0, keepdims=True)  # (1, BD) θ tile
    r = jnp.mean(wk, axis=0, keepdims=True)           # chunk center
    wc, bcc = wk - r, bc - r
    cross = jax.lax.dot_general(                     # (N, K)
        wc, bcc, (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)
    wsq = jnp.sum(wc * wc, axis=1)
    bsq = jnp.sum(bcc * bcc, axis=1)
    d2_ref[...] += wsq[:, None] + bsq[None, :] - 2.0 * cross


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_coalition_stats(w: jax.Array, m: jax.Array, *, block_d: int = 16384,
                          interpret: bool,
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One sweep: barycenters, θ, and medoid distances from a single read.

    Args:
      w: (N, D) client weight matrix.
      m: (K, N) aggregation matrix — weighted membership rows divided by the
        barycenter denominators, empty-coalition fallback rows substituted
        (see ``repro.core.fused.aggregation_matrix``), so ``m @ w`` is the
        finished (K, D) barycenter matrix.

    Returns:
      (b, theta, med_d2): (K, D) barycenters, (D,) global aggregate, and the
      (N, K) squared client→barycenter distances for the medoid election.
    """
    n, d = w.shape
    k = m.shape[0]
    pad = (-d) % block_d
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    dpad = w.shape[1]
    nchunks = dpad // block_d
    b, t, d2 = pl.pallas_call(
        _stats_kernel,
        grid=(nchunks,),
        in_specs=[pl.BlockSpec((k, n), lambda i: (0, 0)),
                  pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=(pl.BlockSpec((k, block_d), lambda i: (0, i)),
                   pl.BlockSpec((1, block_d), lambda i: (0, i)),
                   pl.BlockSpec((n, k), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((k, dpad), jnp.float32),
                   jax.ShapeDtypeStruct((1, dpad), jnp.float32),
                   jax.ShapeDtypeStruct((n, k), jnp.float32)),
        interpret=interpret,
    )(m, w)
    return b[:, :d], t[0, :d], jnp.maximum(d2, 0.0)
