"""Pallas TPU kernel: pairwise squared Euclidean distance over huge D.

The paper's distance d(ω_i, ω_j) runs over flattened model weights, so D is
1e6–1e12 while N (clients) is tiny.  The (N, D) matrix is streamed HBM→VMEM in
D-chunks; each grid step computes the chunk's Gram matrix on the MXU
(``wk @ wk.T``) plus row norms, accumulating

    acc += ‖w_i‖² + ‖w_j‖² − 2·⟨w_i, w_j⟩

into a resident (N, N) VMEM accumulator, on rows centered per chunk (the
chunk's column mean over clients subtracted — distances are
translation-invariant, and uncentered client weights that share one θ would
cancel away most of f32's digits).  This is the TPU adaptation of the
paper's flatten-and-norm: distance becomes a bandwidth-bound streaming matmul
instead of a materialised (N, N, D) difference tensor.

Grid: (D // block_d,), last (only) axis is a reduction — the output block
index_map is constant so the accumulator stays resident in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: full f32 MXU contraction (see ``repro.core.fused.mm``: the Gram form
#: cancels catastrophically at the TPU's default bf16-pass precision)
_HIGHEST = jax.lax.Precision.HIGHEST


def _pairwise_kernel(w_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    wk = w_ref[...].astype(jnp.float32)              # (N, BD)
    wk = wk - jnp.mean(wk, axis=0, keepdims=True)    # centered chunk
    gram = jax.lax.dot_general(                      # (N, N) on the MXU
        wk, wk, (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)
    sq = jnp.sum(wk * wk, axis=1)                    # (N,)
    out_ref[...] += sq[:, None] + sq[None, :] - 2.0 * gram


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def pairwise_sq_dists(w: jax.Array, *, block_d: int = 16384,
                      interpret: bool) -> jax.Array:
    """(N, D) -> (N, N) squared distances, tiled over D.

    VMEM working set: N*block_d*4 bytes for the chunk + N²*4 for the
    accumulator; block_d=16384 with N≤64 is ≈4 MB, comfortably inside the
    ~16 MB v5e VMEM.
    """
    n, d = w.shape
    pad = (-d) % block_d
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    nchunks = w.shape[1] // block_d
    out = pl.pallas_call(
        _pairwise_kernel,
        grid=(nchunks,),
        in_specs=[pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(w)
    # zero the diagonal exactly (dot-form can leave ~1e-6 residue) and clamp
    out = jnp.maximum(out, 0.0)
    return out * (1.0 - jnp.eye(n, dtype=jnp.float32))


def _to_points_kernel(w_ref, p_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    wk = w_ref[...].astype(jnp.float32)              # (N, BD)
    r = jnp.mean(wk, axis=0, keepdims=True)          # chunk center
    wk = wk - r
    pk = p_ref[...].astype(jnp.float32) - r          # (K, BD)
    cross = jax.lax.dot_general(                     # (N, K)
        wk, pk, (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)
    wsq = jnp.sum(wk * wk, axis=1)
    psq = jnp.sum(pk * pk, axis=1)
    out_ref[...] += wsq[:, None] + psq[None, :] - 2.0 * cross


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def sq_dists_to_points(w: jax.Array, p: jax.Array, *, block_d: int = 16384,
                       interpret: bool) -> jax.Array:
    """(N, D), (K, D) -> (N, K) squared distances, tiled over D."""
    n, d = w.shape
    k = p.shape[0]
    pad = (-d) % block_d
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        p = jnp.pad(p, ((0, 0), (0, pad)))
    nchunks = w.shape[1] // block_d
    out = pl.pallas_call(
        _to_points_kernel,
        grid=(nchunks,),
        in_specs=[pl.BlockSpec((n, block_d), lambda i: (0, i)),
                  pl.BlockSpec((k, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(w, p)
    return jnp.maximum(out, 0.0)
