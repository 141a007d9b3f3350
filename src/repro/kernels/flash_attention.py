"""Pallas TPU kernel: tiled online-softmax attention (GQA / causal / sliding
window) — the serving-path compute hot-spot of every attention architecture in
the assigned pool.

TPU adaptation of FlashAttention: Q tiles stay resident in VMEM while K/V
tiles stream HBM→VMEM; softmax statistics (m, l) and the output accumulator
live in VMEM scratch across the innermost (K-block) grid axis, so the
(Sq, Skv) score matrix never materialises in HBM.  MXU does the two GEMMs per
tile; block shapes are multiples of (8, 128) lanes.

Grid: (B, Hq, Sq/block_q, Skv/block_k) — the last axis is the streaming
reduction (init at ik==0, finalize at ik==nk-1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int | None,
                  block_q: int, block_k: int, q_offset: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                  # (BQ, Dh)
    k = k_ref[0, 0].astype(jnp.float32)                  # (BK, Dh)
    v = v_ref[0, 0].astype(jnp.float32)                  # (BK, Dh)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask = kpos <= qpos
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]                                  # (BQ, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)         # (BQ, BK)
    alpha = jnp.exp(m_prev - m_new)                      # (BQ, 1)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool) -> jax.Array:
    """Tiled attention.  q: (B, Hq, Sq, Dh); k, v: (B, Hkv, Skv, Dh).

    Queries are aligned to the END of the K/V timeline (decode-friendly):
    query i has absolute position ``skv - sq + i``.
    """
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    if scale is None:
        scale = dh ** -0.5

    # pad seq lens to block multiples and head dim to lane width
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    pad_d = (-dh) % 128
    if pad_q or pad_d:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, pad_d)))
    if pad_k or pad_d:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, pad_d)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, pad_d)))
    sq_p, skv_p, dh_p = q.shape[2], k.shape[2], q.shape[3]
    nq, nk = sq_p // block_q, skv_p // block_k
    q_offset = skv - sq  # absolute position of the first (real) query

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, q_offset=q_offset, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh_p), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, dh_p),
                         lambda b_, h, i, j, g=group: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, dh_p),
                         lambda b_, h, i, j, g=group: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh_p), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, dh_p), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh_p), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq, :dh]
