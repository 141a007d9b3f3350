"""Mixture-of-Experts layer: token-choice top-k routing with sort-based,
capacity-bounded dispatch (TPU-idiomatic — no (T, E, C) one-hot dispatch
einsum, whose FLOPs would dwarf the expert compute at kimi-k2 scale).

Dispatch: flatten (token, choice) pairs, stable-argsort by expert id, compute
within-expert slots by cumsum, drop beyond-capacity entries, gather tokens
into an (E, C, d) buffer, run the batched SwiGLU expert FFN on the MXU, and
scatter-add gated outputs back.  All shapes static; capacity
C = ceil(cf * T * top_k / E).

Returns the Switch-style load-balance auxiliary loss alongside the output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import dense_init


def moe_init(key, cfg: ModelConfig) -> dict:
    dt = jnp.dtype(cfg.dtype)
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    kr, k1, k2, k3 = jax.random.split(key, 4)

    def experts(k, d_in, d_out, scale):
        w = jax.random.normal(k, (e, d_in, d_out), jnp.float32) * scale
        return w.astype(dt)

    return {
        "router": dense_init(kr, d, e, jnp.float32),
        "wi_gate": experts(k1, d, ff, d ** -0.5),
        "wi_up": experts(k2, d, ff, d ** -0.5),
        "wo": experts(k3, ff, d, ff ** -0.5),
    }


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(-(-cfg.capacity_factor * n_tokens * cfg.top_k // cfg.n_experts))
    return max(c, 4)


# --- expert parallelism (shard_map) ------------------------------------------
# When enabled, moe_apply routes through a hand-written expert-parallel
# implementation: tokens all_to_all to the ranks owning their experts (EP
# groups = the `data` mesh axis, experts sharded contiguously over it; the
# expert FFN hidden stays Megatron-sharded over `model` with a psum).
# GSPMD cannot infer this from the sort-based dispatch's gathers/scatters —
# it all-gathers expert weights instead (EXPERIMENTS.md §Perf, kimi-k2).
_EP: dict = {"mesh": None, "token_axes": ("data",), "expert_axis": "data",
             "model_axis": "model"}


def enable_expert_parallel(mesh, *, token_axes=("data",), expert_axis="data",
                           model_axis="model") -> None:
    _EP.update(mesh=mesh, token_axes=tuple(token_axes),
               expert_axis=expert_axis, model_axis=model_axis)


def disable_expert_parallel() -> None:
    _EP["mesh"] = None


def moe_apply(params: dict, cfg: ModelConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux load-balance loss ())."""
    mesh = _EP["mesh"]
    if mesh is not None and cfg.n_experts % mesh.shape[_EP["expert_axis"]] == 0:
        return moe_apply_ep(params, cfg, x, mesh=mesh,
                            token_axes=_EP["token_axes"],
                            expert_axis=_EP["expert_axis"],
                            model_axis=_EP["model_axis"])
    return _moe_apply_gspmd(params, cfg, x)


def _moe_apply_gspmd(params: dict, cfg: ModelConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Baseline: global sort-based dispatch, sharding left to GSPMD."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = capacity(cfg, t)
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ params["router"])           # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)                # (T, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- sort-based dispatch ----
    flat_e = expert_idx.reshape(-1)                                # (T*K,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]                                       # (T*K,)
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)          # (E,)
    starts = jnp.cumsum(counts) - counts                           # exclusive
    pos_in_expert = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_expert < cap
    slot = jnp.where(keep, sorted_e * cap + pos_in_expert, e * cap)
    src_token = order // k                                         # (T*K,)

    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], xt[src_token], 0.0),
                           mode="drop")
    buf = buf[: e * cap].reshape(e, cap, d)

    # ---- batched SwiGLU expert FFN (MXU) ----
    h_gate = jnp.einsum("ecd,edf->ecf", buf, params["wi_gate"])
    h_up = jnp.einsum("ecd,edf->ecf", buf, params["wi_up"])
    h = jax.nn.silu(h_gate.astype(jnp.float32)).astype(x.dtype) * h_up
    out_e = jnp.einsum("ecf,efd->ecd", h, params["wo"])            # (E, C, d)

    # ---- combine ----
    out_flat = out_e.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None], out_flat[jnp.minimum(slot, e * cap - 1)], 0.0)
    gate_sorted = gate_vals.reshape(-1)[order]
    contrib = gathered * gate_sorted[:, None].astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[src_token].add(contrib)

    # ---- Switch load-balance aux ----
    frac_tokens = counts.astype(jnp.float32) / jnp.float32(t * k)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * mean_prob)

    return out.reshape(b, s, d), aux


def _sort_dispatch(x_flat, ids, n_buckets, cap):
    """Sort rows of x_flat by bucket id; place into (n_buckets, cap, d).

    ids may contain -1 (invalid -> dropped).  Returns (buf, slot, keep):
    ``slot`` maps each input row to its flat buffer slot (undefined where
    ``keep`` is False).
    """
    m, d = x_flat.shape
    ids_sortkey = jnp.where(ids < 0, n_buckets, ids)
    order = jnp.argsort(ids_sortkey, stable=True)
    sorted_ids = ids_sortkey[order]
    counts = jnp.zeros((n_buckets + 1,), jnp.int32).at[ids_sortkey].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(m, dtype=jnp.int32) - starts[sorted_ids]
    keep_sorted = (pos < cap) & (sorted_ids < n_buckets)
    slot_sorted = jnp.where(keep_sorted, sorted_ids * cap + pos, n_buckets * cap)
    buf = jnp.zeros((n_buckets * cap + 1, d), x_flat.dtype)
    buf = buf.at[slot_sorted].set(
        jnp.where(keep_sorted[:, None], x_flat[order], 0.0), mode="drop")
    # scatter slot back to input order
    slot = jnp.zeros((m,), jnp.int32).at[order].set(slot_sorted)
    keep = jnp.zeros((m,), bool).at[order].set(keep_sorted)
    return buf[:-1].reshape(n_buckets, cap, d), slot, keep


def moe_apply_ep(params: dict, cfg: ModelConfig, x: jax.Array, *, mesh,
                 token_axes=("data",), expert_axis="data",
                 model_axis="model") -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE: shard_map + all_to_all (TPU-native dispatch).

    Layout: tokens sharded over ``token_axes``; experts contiguously sharded
    over ``expert_axis`` (a member of token_axes); expert FFN hidden sharded
    over ``model_axis`` (Megatron, psum to combine).  Per EP group of R ranks:

      route -> bucket (token,choice) pairs by owner rank -> all_to_all ->
      local sort-dispatch to the rank's E/R experts -> batched SwiGLU ->
      all_to_all back -> gate-weighted combine.
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    r = mesh.shape[expert_axis]
    e_local = e // r
    mdl = mesh.shape[model_axis] if model_axis in mesh.axis_names else 1

    def local_fn(router, wig, wiu, wo, xl):
        # xl: (B_l, S, d); wig/wiu: (E_l, d, ff_l); wo: (E_l, ff_l, d)
        bl = xl.shape[0]
        t_l = bl * s
        xt = xl.reshape(t_l, d)
        logits = xt.astype(jnp.float32) @ router               # (T_l, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)        # (T_l, K)
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

        flat_e = expert_idx.reshape(-1)                        # (T_l*K,)
        src_token = jnp.arange(t_l * k, dtype=jnp.int32) // k
        dest_rank = flat_e // e_local
        cap_s = max(4, -(-int(cfg.capacity_factor * t_l * k) // r))
        send, slot_send, keep_send = _sort_dispatch(
            xt[src_token], dest_rank, r, cap_s)                # (R, C_s, d)
        # ship local expert ids alongside (as an extra feature column)
        meta_vals = (flat_e % e_local).astype(jnp.float32)[:, None]
        meta_buf, _, _ = _sort_dispatch(meta_vals, dest_rank, r, cap_s)
        # mark empty slots invalid: a zero row could be a real token, so use
        # a parallel validity channel
        ones = jnp.ones((t_l * k, 1), jnp.float32)
        valid_buf, _, _ = _sort_dispatch(ones, dest_rank, r, cap_s)

        recv = jax.lax.all_to_all(send, expert_axis, 0, 0, tiled=False)
        meta_r = jax.lax.all_to_all(meta_buf, expert_axis, 0, 0, tiled=False)
        valid_r = jax.lax.all_to_all(valid_buf, expert_axis, 0, 0, tiled=False)

        m = r * cap_s
        x_in = recv.reshape(m, d)
        ids_in = jnp.where(valid_r.reshape(m) > 0.5,
                           meta_r.reshape(m).astype(jnp.int32), -1)
        cap_e = max(4, int(-(-cfg.capacity_factor * m // e_local)))
        buf, slot_e, keep_e = _sort_dispatch(x_in, ids_in, e_local, cap_e)

        h_g = jnp.einsum("ecd,edf->ecf", buf, wig)
        h_u = jnp.einsum("ecd,edf->ecf", buf, wiu)
        h = jax.nn.silu(h_g.astype(jnp.float32)).astype(buf.dtype) * h_u
        out_e = jnp.einsum("ecf,efd->ecd", h, wo)              # partial (ff_l)
        # NOTE: the model-axis psum happens AFTER the combine at the source
        # rank, on (T_l, d) token rows — 10-12x fewer rows than the
        # (E_l, C_e, d) expert buffer (EXPERIMENTS.md §Perf, HC1 iter 3).

        out_flat = out_e.reshape(e_local * cap_e, d)
        out_rows = jnp.where(
            keep_e[:, None],
            out_flat[jnp.minimum(slot_e, e_local * cap_e - 1)], 0.0)
        back = jax.lax.all_to_all(out_rows.reshape(r, cap_s, d),
                                  expert_axis, 0, 0, tiled=False)
        back_flat = back.reshape(r * cap_s, d)
        contrib = jnp.where(
            keep_send[:, None],
            back_flat[jnp.minimum(slot_send, r * cap_s - 1)], 0.0)
        contrib = contrib * gate_vals.reshape(-1)[:, None].astype(contrib.dtype)
        out = jnp.zeros((t_l, d), xl.dtype).at[src_token].add(contrib)
        if mdl > 1:
            out = jax.lax.psum(out, model_axis)

        # Switch aux (global): fractions over ALL tokens/experts in the group
        counts_g = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0)
        counts_g = jax.lax.psum(counts_g, token_axes)
        probs_sum = jax.lax.psum(jnp.sum(probs, 0), token_axes)
        t_total = t_l * int(np.prod([mesh.shape[a] for a in token_axes]))
        aux = e * jnp.sum((counts_g / (t_total * k)) * (probs_sum / t_total))
        return out.reshape(bl, s, d), aux

    tok_spec = P(token_axes, None, None)
    out_specs = (tok_spec, P())
    in_specs = (P(), P(expert_axis, None, model_axis),
                P(expert_axis, None, model_axis),
                P(expert_axis, model_axis, None), tok_spec)
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out, aux = fn(params["router"], params["wi_gate"], params["wi_up"],
                  params["wo"], x)
    return out, aux
