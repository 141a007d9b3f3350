"""Pallas TPU kernel: coalition barycenter segment-sum.

``b = onehot @ W`` with onehot (K, N) membership and W (N, D) client weights.
K and N are tiny; D is the model dimension (up to 1e12), so the kernel tiles D
and emits one (K, block_d) output tile per grid step — a pure streaming matmul
with no accumulator revisits (each output tile is written exactly once).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: full f32 MXU contraction (see ``repro.core.fused.mm``: the Gram form
#: cancels catastrophically at the TPU's default bf16-pass precision)
_HIGHEST = jax.lax.Precision.HIGHEST


def _segment_kernel(onehot_ref, w_ref, out_ref):
    oh = onehot_ref[...].astype(jnp.float32)          # (K, N)
    wk = w_ref[...].astype(jnp.float32)               # (N, BD)
    out_ref[...] = jax.lax.dot_general(
        oh, wk, (((1,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def segment_sum(onehot: jax.Array, w: jax.Array, *, block_d: int = 16384,
                interpret: bool) -> jax.Array:
    """(K, N) @ (N, D) -> (K, D), D-tiled."""
    k, n = onehot.shape
    d = w.shape[1]
    pad = (-d) % block_d
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
    nchunks = w.shape[1] // block_d
    out = pl.pallas_call(
        _segment_kernel,
        grid=(nchunks,),
        in_specs=[pl.BlockSpec((k, n), lambda i: (0, 0)),
                  pl.BlockSpec((n, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((k, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, w.shape[1]), jnp.float32),
        interpret=interpret,
    )(onehot, w)
    return out[:, :d]
