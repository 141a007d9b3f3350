"""Serving driver: LM generation or coalition-routed federation serving.

Modes:
  lm    (default) — prefill a batch of prompts through a (reduced or full)
        assigned architecture, then decode N tokens with the
        scan-over-layers KV-cache/SSM-state serving path.
  fl    — the consumer half of the train/serve pair: attach to a
        :class:`repro.serve.ModelStore` that a federation run is publishing
        into (``train.py --snapshot-dir``), build the coalition routing
        table from the latest snapshot, and answer batched queries where
        each query runs through its client's coalition barycenter (unknown
        clients get the global model).  Polls the store between batches and
        hot-swaps newer rounds without recompiling.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch falcon-mamba-7b \
      --reduced --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --mode fl \
      --store-dir /tmp/fl-store --batch 32 --repeat 8

Model size: ``--reduced`` (the default — CPU-smoke scale) and ``--full``
are an explicit mutually exclusive pair.  Earlier versions defaulted
``--reduced`` to True *and* accepted both flags at once, so passing
``--reduced`` was a silent no-op and ``--reduced --full`` meant full;
now the pair is validated and the default is documented.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get, reduced
from repro.data import synthetic
from repro.launch import compile_cache
from repro.models import transformer as tf


def generate(params, cfg, batch, *, max_new: int, cache_len: int,
             greedy: bool = True, key=None):
    """Prefill + autoregressive decode.  Returns (tokens (B, max_new), stats)."""
    b = batch["tokens"].shape[0]
    cache = tf.init_cache(cfg, b, cache_len)
    t0 = time.time()
    logits, cache = jax.jit(
        lambda p, bt, c: tf.prefill(p, cfg, bt, c))(params, batch, cache)
    prefill_s = time.time() - t0

    decode_jit = jax.jit(lambda p, t, c: tf.decode_step(p, cfg, t, c))
    toks = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t0 = time.time()
    for i in range(max_new):
        toks.append(tok)
        logits, cache = decode_jit(params, tok, cache)
        if greedy or key is None:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            key, sk = jax.random.split(key)
            tok = jax.random.categorical(sk, logits).astype(jnp.int32)
    decode_s = time.time() - t0
    out = jnp.stack(toks, axis=1)
    return out, {"prefill_s": round(prefill_s, 3),
                 "decode_s_per_tok": round(decode_s / max_new, 4)}


def make_apply_fn(model: str, arch: str, use_reduced: bool):
    """``(params, x) -> outputs`` for a served model family.

    ``cnn`` serves (B, 28, 28, 1) images -> (B, 10) logits (the paper's
    federated model); ``transformer`` serves (B, T) token batches ->
    (B, T, vocab) logits through the assigned architecture.
    """
    if model == "cnn":
        from repro.models import cnn

        return cnn.apply, lambda b, seed: jax.random.normal(
            jax.random.key(seed), (b, 28, 28, 1), jnp.float32)
    cfg = get(arch)
    if use_reduced:
        cfg = reduced(cfg)
    if cfg.modality or cfg.enc_dec:
        raise SystemExit(
            f"--mode fl serves token-only architectures; {cfg.name} needs "
            "modal inputs (use --mode lm for its generate path)")

    def apply_fn(params, toks):
        return tf.forward(params, cfg, {"tokens": toks})[0]

    def make_queries(b, seed):
        return jnp.asarray(synthetic.lm_tokens(b, 16, cfg.vocab, seed=seed))

    return apply_fn, make_queries


def run_fl_serve(args) -> dict:
    """Attach to a ModelStore and serve routed batches from its latest round."""
    from repro.serve import GLOBAL, BatchServer, ModelStore

    store = ModelStore(args.store_dir)
    deadline = time.time() + args.wait
    while store.latest_round() is None:
        if time.time() >= deadline:
            raise SystemExit(
                f"no snapshots under {args.store_dir} after {args.wait}s — "
                "is a train.py --snapshot-dir run publishing there?")
        time.sleep(0.2)
    snap = store.load()
    apply_fn, make_queries = make_apply_fn(args.model, args.arch,
                                           args.reduced)
    server = BatchServer(apply_fn, snap)

    n_known = snap.assignment.size
    # query ids sweep the known population plus one stranger per batch, so
    # every batch exercises both coalition routing and the global fallback
    ids = np.arange(args.batch) % (n_known + 1)
    ids = np.where(ids == n_known, -1, ids)
    # the serve-side run ledger: one serve_batch record per answered batch
    # (same sink contract as the training ledger — see docs/observability.md)
    from repro import obs

    sink = (obs.make_sink("jsonl", path=args.metrics_out)
            if args.metrics_out else None)
    swaps = served = 0
    checksum = 0.0
    t0 = time.time()
    for i in range(args.repeat):
        swaps += int(server.poll(store))      # hot-swap newer rounds
        tb = time.perf_counter()
        out = server.serve(ids, make_queries(args.batch, args.seed + i))
        served += int(out.shape[0])
        checksum += float(jnp.sum(out))       # blocks; keeps timing honest
        if sink is not None:
            c = server.stats
            sink.emit({
                "schema": obs.OBS_SCHEMA, "kind": obs.SERVE_BATCH,
                "batch": i, "round": server.round,
                "batch_ms": round((time.perf_counter() - tb) * 1e3, 3),
                **c,
                "poll_hit_rate": round(c["poll_hits"] / max(c["polls"], 1),
                                       4),
                "fallback_rate": round(
                    c["fallback_queries"] / max(c["queries"], 1), 4)})
    wall = time.time() - t0
    if sink is not None:
        sink.close()
    assert np.isfinite(checksum), "served logits contain NaN/Inf"
    routes = server.routing.route(ids)
    c = server.stats
    stats = {
        "mode": "fl", "model": args.model, "store": args.store_dir,
        "round": server.round, "published_rounds": store.rounds(),
        "n_coalitions": int(snap.barycenters.shape[0]),
        "batch": args.batch, "repeat": args.repeat,
        "queries_per_s": round(served / wall, 1),
        "global_fallback_queries": int(np.sum(routes == GLOBAL)),
        "hot_swaps": swaps,
        "compile_count": server.compile_count,
        "swap_ms_mean": round(c["swap_ms_total"] / max(c["swaps"], 1), 3),
        "poll_hit_rate": round(c["poll_hits"] / max(c["polls"], 1), 4),
        "fallback_rate": round(c["fallback_queries"] / max(c["queries"], 1),
                               4),
    }
    if args.metrics_out:
        stats["metrics_out"] = args.metrics_out
    print(json.dumps(stats, indent=1))
    return stats


def run_lm(args) -> dict:
    cfg = get(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params = tf.init(jax.random.key(args.seed), cfg)
    toks = synthetic.lm_tokens(args.batch, args.prompt_len, cfg.vocab,
                               seed=args.seed)
    batch = {"tokens": jnp.asarray(toks)}
    if cfg.modality:
        batch["modal"] = jax.random.normal(
            jax.random.key(1), (args.batch, cfg.n_modal_tokens, cfg.d_modal),
            jnp.float32)
    prefix = cfg.n_modal_tokens if (cfg.modality and not cfg.enc_dec) else 0
    out, stats = generate(params, cfg, batch,
                          max_new=args.gen,
                          cache_len=prefix + args.prompt_len + args.gen,
                          key=jax.random.key(args.seed + 2))
    assert not bool(jnp.any(jnp.isnan(out.astype(jnp.float32))))
    result = {"arch": cfg.name, "generated_shape": list(out.shape),
              "first_seq": [int(t) for t in out[0][:8]], **stats}
    print(json.dumps(result))
    return result


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", default="lm", choices=["lm", "fl"])
    # lm + fl(transformer)
    ap.add_argument("--arch", default="falcon-mamba-7b")
    size = ap.add_mutually_exclusive_group()
    size.add_argument("--reduced", dest="reduced", action="store_true",
                      help="serve the reduced (CPU-smoke) config [default]")
    size.add_argument("--full", dest="reduced", action="store_false",
                      help="serve the full-size config (real hardware)")
    ap.set_defaults(reduced=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--flash", action="store_true",
                    help="route attention through the Pallas flash kernel")
    # fl (ModelStore consumer)
    ap.add_argument("--store-dir", default=None,
                    help="ModelStore directory a federation run publishes "
                         "into (required for --mode fl)")
    ap.add_argument("--model", default="cnn", choices=["cnn", "transformer"],
                    help="served model family; must match what the "
                         "publishing run trained")
    ap.add_argument("--repeat", type=int, default=4,
                    help="number of batches to serve (polling the store "
                         "for newer rounds between batches)")
    ap.add_argument("--wait", type=float, default=0.0,
                    help="seconds to wait for the first published snapshot")
    ap.add_argument("--metrics-out", default=None,
                    help="stream per-batch serve counters (queries/s, swap "
                         "latency, poll hit/miss, routing fallback rate) to "
                         "this JSONL file via the repro.obs ledger")
    return ap


def main() -> None:
    args = build_parser().parse_args()
    compile_cache.enable()

    if args.flash:
        from repro.models.layers import set_flash_kernel

        set_flash_kernel(True)
    if args.mode == "fl":
        if args.store_dir is None:
            raise SystemExit("--mode fl requires --store-dir")
        run_fl_serve(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
