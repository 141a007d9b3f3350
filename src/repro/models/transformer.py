"""Decoder-only LM stack covering dense / MoE / SSM / hybrid / VLM families.

Layers are stacked with a leading L axis (vmap-initialised) and applied with
``lax.scan`` so the HLO is O(1) in depth — essential for lowering 28–64-layer
configs on the 512-device dry-run mesh.

Entry points:
  init(key, cfg)                      -> params
  forward(params, cfg, batch)         -> logits            (train / eval)
  loss_fn(params, cfg, batch)         -> scalar            (next-token CE)
  init_cache(cfg, batch, max_len)     -> cache pytree
  prefill(params, cfg, batch, cache)  -> (logits, cache)
  decode_step(params, cfg, tok, cache, index) -> (logits, cache)

Batch layout: {'tokens': (B, S) int32[, 'modal': (B, P, d_modal)]}.
VLM/audio frontends are stubs per the brief: 'modal' carries precomputed
patch/frame embeddings which a learned linear projector maps to d_model and
prepends to the token sequence.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (attention_apply, attention_init, dense_init,
                                 mlp_apply, mlp_init, rmsnorm, rmsnorm_init)

PyTree = Any

# --- per-layer block ----------------------------------------------------------

def block_init(key, cfg: ModelConfig, *, cross: bool = False) -> dict:
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    p: dict = {"ln1": rmsnorm_init(cfg.d_model, dt)}
    if not cfg.attn_free:
        p["attn"] = attention_init(ks[0], cfg)
    if cfg.ssm or cfg.hybrid:
        p["ssm"] = ssm_mod.ssm_init(ks[1], cfg)
    if cross:
        p["ln_cross"] = rmsnorm_init(cfg.d_model, dt)
        p["cross"] = attention_init(ks[4], cfg)
    if cfg.moe:
        p["ln2"] = rmsnorm_init(cfg.d_model, dt)
        p["moe"] = moe_mod.moe_init(ks[2], cfg)
    elif cfg.d_ff > 0 and not cfg.ssm:
        p["ln2"] = rmsnorm_init(cfg.d_model, dt)
        p["mlp"] = mlp_init(ks[3], cfg)
    return p


def _mixer(p: dict, cfg: ModelConfig, h: jax.Array, *, positions,
           cache=None, cache_index=None, ssm_state=None, causal=True):
    """Token mixer: attention, SSM, or both in parallel (hymba)."""
    new_cache, new_ssm = None, None
    outs = []
    if not cfg.attn_free:
        a, new_cache = attention_apply(p["attn"], cfg, h, positions=positions,
                                       cache=cache, cache_index=cache_index,
                                       causal=causal)
        outs.append(a)
    if cfg.ssm or cfg.hybrid:
        if ssm_state is not None and h.shape[1] == 1:
            s, new_ssm = ssm_mod.ssm_step(p["ssm"], cfg, h, ssm_state)
        elif ssm_state is not None:
            # multi-token prefill: run the chunked scan from the carried state
            s, new_ssm = ssm_mod.ssm_apply(p["ssm"], cfg, h, state=ssm_state,
                                           return_state=True)
        else:
            s = ssm_mod.ssm_apply(p["ssm"], cfg, h)
        outs.append(s)
    mix = outs[0] if len(outs) == 1 else 0.5 * (outs[0] + outs[1])
    return mix, new_cache, new_ssm


def block_apply(p: dict, cfg: ModelConfig, x: jax.Array, *, positions,
                cache=None, cache_index=None, ssm_state=None,
                memory=None, causal=True):
    """Pre-norm residual block.  Returns (x, new_cache, new_ssm_state, aux)."""
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    mix, new_cache, new_ssm = _mixer(p, cfg, h, positions=positions,
                                     cache=cache, cache_index=cache_index,
                                     ssm_state=ssm_state, causal=causal)
    x = x + mix
    if "cross" in p and memory is not None:
        hc = rmsnorm(p["ln_cross"], x, cfg.norm_eps)
        c, _ = attention_apply(p["cross"], cfg, hc, positions=positions,
                               memory=memory)
        x = x + c
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
        m, aux = moe_mod.moe_apply(p["moe"], cfg, h2)
        x = x + m
    elif "mlp" in p:
        h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
        x = x + mlp_apply(p["mlp"], cfg, h2)
    return x, new_cache, new_ssm, aux


# --- model ---------------------------------------------------------------------

def init(key, cfg: ModelConfig) -> PyTree:
    dt = jnp.dtype(cfg.dtype)
    ke, kl, kh, kp, kenc = jax.random.split(key, 5)
    params: dict = {
        # GPT-style 0.02 init keeps tied-head logits O(1) after the final
        # norm; rows padded to cfg.vocab_pad multiples for sharding
        "embed": dense_init(ke, cfg.padded_vocab, cfg.d_model, dt, scale=0.02),
        "ln_f": rmsnorm_init(cfg.d_model, dt),
    }
    lkeys = jax.random.split(kl, cfg.n_layers)
    params["layers"] = jax.vmap(
        lambda k: block_init(k, cfg, cross=cfg.enc_dec))(lkeys)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kh, cfg.d_model, cfg.padded_vocab, dt)
    if cfg.modality:
        params["proj"] = dense_init(kp, cfg.d_modal, cfg.d_model, dt)
    if cfg.enc_dec:
        from repro.models import encdec  # local import to avoid cycle

        params["encoder"] = encdec.encoder_init(kenc, cfg)
    return params


def _embed_inputs(params, cfg: ModelConfig, batch) -> tuple[jax.Array, int]:
    """Token (+ modal prefix) embeddings.  Returns (x (B,S',d), n_prefix)."""
    tok = params["embed"][batch["tokens"]]                    # (B, S, d)
    n_prefix = 0
    if cfg.modality and not cfg.enc_dec and "modal" in batch:
        pre = batch["modal"].astype(tok.dtype) @ params["proj"]
        tok = jnp.concatenate([pre, tok], axis=1)
        n_prefix = pre.shape[1]
    return tok, n_prefix


def _lm_logits(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = (x @ params["embed"].T if cfg.tie_embeddings
              else x @ params["lm_head"])
    if cfg.padded_vocab != cfg.vocab:
        logits = logits[..., : cfg.vocab]
    return logits


def forward(params, cfg: ModelConfig, batch, *,
            remat: bool = False) -> tuple[jax.Array, jax.Array]:
    """Full-sequence causal forward.  Returns (logits, moe_aux).

    ``remat=True`` checkpoints each layer-scan body: only the per-layer
    boundary activations persist to the backward pass, the standard
    scan-over-layers rematerialisation policy.
    """
    if cfg.enc_dec:
        from repro.models import encdec

        return encdec.forward(params, cfg, batch, remat=remat)
    x, _ = _embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(x, lp):
        x, _, _, aux = block_apply(lp, cfg, x, positions=positions)
        return x, aux

    if remat:
        body = jax.checkpoint(body)
    x, auxes = jax.lax.scan(body, x, params["layers"])
    return _lm_logits(params, cfg, x), jnp.sum(auxes)


def loss_fn(params, cfg: ModelConfig, batch, *, aux_coef: float = 0.01,
            remat: bool = False) -> jax.Array:
    """Next-token cross-entropy (text positions only) + MoE aux loss."""
    logits, aux = forward(params, cfg, batch, remat=remat)
    tokens = batch["tokens"]
    n_prefix = logits.shape[1] - tokens.shape[1]
    logits = logits[:, n_prefix:, :]
    lg = logits[:, :-1].astype(jnp.float32)
    tg = tokens[:, 1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tg[..., None].astype(jnp.int32), axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    return ce + aux_coef * aux


# --- serving -------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None, *, ring: bool = False) -> PyTree:
    """Stacked (leading L) decode state for scan-over-layers serving.

    ``ring=True`` (sliding-window archs only): allocate a ``window``-slot
    ring buffer instead of the full timeline — O(window) memory for
    arbitrarily long decode (see EXPERIMENTS.md §Perf, hymba long_500k).
    """
    dt = jnp.dtype(dtype or cfg.dtype)
    L = cfg.n_layers
    cache: dict = {"index": jnp.zeros((), jnp.int32)}
    if not cfg.attn_free:
        kv_len = max_len
        if ring and cfg.window is not None:
            kv_len = min(max_len, cfg.window)
        kv = (L, batch, cfg.n_kv_heads, kv_len, cfg.head_dim)
        cache["k"] = jnp.zeros(kv, dt)
        cache["v"] = jnp.zeros(kv, dt)
    if cfg.ssm or cfg.hybrid:
        cache["conv"] = jnp.zeros((L, batch, cfg.ssm_conv - 1, cfg.d_inner), dt)
        cache["h"] = jnp.zeros((L, batch, cfg.d_inner, cfg.ssm_state), jnp.float32)
    if cfg.enc_dec:
        cache["memory"] = jnp.zeros((batch, cfg.n_modal_tokens, cfg.d_model), dt)
    return cache


def _stacked_layer_state(cache, cfg: ModelConfig):
    """Split the cache into per-layer scanned parts + static extras."""
    parts = {}
    for name in ("k", "v", "conv", "h"):
        if name in cache:
            parts[name] = cache[name]
    return parts


def _step(params, cfg: ModelConfig, x: jax.Array, cache, positions):
    """Advance the layer stack one (or more) token(s) with cached state."""
    idx = cache["index"]
    layer_state = _stacked_layer_state(cache, cfg)
    memory = cache.get("memory")

    def body(x, scanned):
        lp, st = scanned
        attn_cache = {"k": st["k"], "v": st["v"]} if "k" in st else None
        ssm_state = ({"conv": st["conv"], "h": st["h"]}
                     if "conv" in st else None)
        x, new_attn, new_ssm, _ = block_apply(
            lp, cfg, x, positions=positions,
            cache=attn_cache, cache_index=idx, ssm_state=ssm_state,
            memory=memory)
        new_st = {}
        if new_attn is not None:
            new_st.update(new_attn)
        if new_ssm is not None:
            new_st.update(new_ssm)
        return x, new_st

    x, new_state = jax.lax.scan(body, x, (params["layers"], layer_state))
    new_cache = dict(cache)
    new_cache.update(new_state)
    new_cache["index"] = idx + x.shape[1]
    return x, new_cache


def prefill(params, cfg: ModelConfig, batch, cache) -> tuple[jax.Array, PyTree]:
    """Run the prompt through the stack, filling the cache.

    Returns logits for the LAST position (B, vocab) and the filled cache.
    """
    if cfg.enc_dec:
        from repro.models import encdec

        return encdec.prefill(params, cfg, batch, cache)
    x, _ = _embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s)) + cache["index"]
    x, cache = _step(params, cfg, x, cache, positions)
    return _lm_logits(params, cfg, x[:, -1:, :])[:, 0], cache


def decode_step(params, cfg: ModelConfig, token: jax.Array,
                cache) -> tuple[jax.Array, PyTree]:
    """One decode step.  token: (B,) or (B, 1) int32 -> (logits (B, vocab), cache)."""
    if token.ndim == 1:
        token = token[:, None]
    x = params["embed"][token]                                 # (B, 1, d)
    b = x.shape[0]
    positions = jnp.broadcast_to(cache["index"][None, None], (b, 1))
    x, cache = _step(params, cfg, x, cache, positions)
    return _lm_logits(params, cfg, x)[:, 0], cache
