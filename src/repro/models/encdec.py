"""Encoder-decoder backbone (seamless-m4t-large-v2's transformer).

Per the brief's carve-out, the modality frontend (mel-spectrogram + conformer
feature extractor) is a STUB: the batch supplies precomputed frame embeddings
(B, T, d_modal), a learned linear projector lifts them to d_model, and a
bidirectional transformer encoder produces the cross-attention memory.  The
decoder is the shared scan-over-layers stack from ``transformer.py`` with
per-layer cross-attention.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init, rmsnorm, rmsnorm_init


def _enc_cfg(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, enc_dec=False, n_layers=cfg.n_enc_layers,
                               modality=None)


def encoder_init(key, cfg: ModelConfig) -> dict:
    from repro.models import transformer as tf

    ecfg = _enc_cfg(cfg)
    kp, kl = jax.random.split(key)
    lkeys = jax.random.split(kl, ecfg.n_layers)
    return {
        "proj": dense_init(kp, cfg.d_modal, cfg.d_model, jnp.dtype(cfg.dtype)),
        "layers": jax.vmap(lambda k: tf.block_init(k, ecfg))(lkeys),
        "ln_f": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.dtype)),
    }


def encode(params, cfg: ModelConfig, modal: jax.Array, *,
           remat: bool = False) -> jax.Array:
    """modal: (B, T, d_modal) frame embeddings -> memory (B, T, d_model)."""
    from repro.models import transformer as tf

    ecfg = _enc_cfg(cfg)
    enc = params["encoder"]
    x = modal.astype(jnp.dtype(cfg.dtype)) @ enc["proj"]
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(x, lp):
        x, _, _, _ = tf.block_apply(lp, ecfg, x, positions=positions,
                                    causal=False)
        return x, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, enc["layers"])
    return rmsnorm(enc["ln_f"], x, cfg.norm_eps)


def forward(params, cfg: ModelConfig, batch, *,
            remat: bool = False) -> tuple[jax.Array, jax.Array]:
    """Training forward: encode modal frames, decode tokens with cross-attn."""
    from repro.models import transformer as tf

    memory = encode(params, cfg, batch["modal"], remat=remat)
    x = params["embed"][batch["tokens"]]
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(x, lp):
        x, _, _, aux = tf.block_apply(lp, cfg, x, positions=positions,
                                      memory=memory)
        return x, aux

    if remat:
        body = jax.checkpoint(body)
    x, auxes = jax.lax.scan(body, x, params["layers"])
    return tf._lm_logits(params, cfg, x), jnp.sum(auxes)


def prefill(params, cfg: ModelConfig, batch, cache):
    """Encode memory into the cache, then prefill the decoder prompt."""
    from repro.models import transformer as tf

    memory = encode(params, cfg, batch["modal"])
    cache = dict(cache)
    cache["memory"] = memory.astype(cache["memory"].dtype)
    x = params["embed"][batch["tokens"]]
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s)) + cache["index"]
    x, cache = tf._step(params, cfg, x, cache, positions)
    return tf._lm_logits(params, cfg, x[:, -1:, :])[:, 0], cache
