"""Two-pass fused coalition round — Algorithm 1's server step as a streaming
program (the ``Backend.fused_round`` primitive).

The composed round is bandwidth-profligate on an accelerator: one round
touches W-sized data five times (assignment distances, a materialised (K, D)
center gather, the barycenter segment-sum, the medoid distances, and the
empty-coalition ``where``).  At framework scale (D >= 1e9, N tiny) the round
is purely HBM-bandwidth-bound, so passes over W *are* the round time.  This
module collapses Steps II-IV to two sweeps:

  pass 1 — one sweep over D-chunks accumulates the (N, K) assignment
           distances, reading the K center rows straight out of each resident
           (N, block_d) chunk via ``center_idx`` — no (K, D) center gather
           ever materialises.
  pass 2 — one sweep accumulates, per chunk: the weighted segment sums (the
           barycenter numerators), the (N, K) client->barycenter distances
           that drive the medoid update, and the θ partial sums — so
           barycenters, medoids, and the global aggregate cost one read of W
           instead of three.

The empty-coalition fallback (keep the previous center's weights) folds into
the aggregation matrix itself: a zero-mass coalition's one-hot row is replaced
by the indicator of its previous center with unit mass, so the fallback is
part of the same matmul — no extra pass, and it works identically on every
backend.

Implementations (registered through :mod:`repro.core.backends`):

  :func:`fused_round_xla`     — ``lax.scan`` streaming composition, chunk
                                partition and accumulation order identical to
                                the composed xla path (bit-for-bit equal —
                                the reference).
  :func:`fused_round_dot`     — Gram form: the medoid distances come out of
                                the pass-1 (N, N) Gram matrix
                                (⟨w_i, b_j⟩ = (G · M^T)_ij), so only the
                                segment matmul re-reads W.
  :func:`fused_round_pallas`  — the :mod:`repro.kernels.fused_round` TPU
                                kernels (lazy import; interpret-mode on CPU).
  :func:`compose_fused_round` — generic fall-back built only from the three
                                base primitives, so third-party backends that
                                predate ``Backend.fused_round`` keep working
                                through the same entry point.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import backends as bk
from repro.core import instrument
from repro.core import sketch as sk_mod
from repro.obs import metrics as obs_metrics


class FusedStats(NamedTuple):
    """What a backend's ``fused_round`` primitive produces (pre-medoid-argmin)."""

    assignment: jax.Array   # (N,) int32 coalition id per client (centers pinned)
    barycenters: jax.Array  # (K, D) float32, empty coalitions already replaced
    counts: jax.Array       # (K,) float32 member mass (pre-fallback; 0 if empty)
    med_d2: jax.Array       # (N, K) float32 squared dists client -> barycenter
    theta: jax.Array        # (D,) float32 global aggregate (mean of barycenters)


class FusedRound(NamedTuple):
    """A full Algorithm-1 round out of :func:`fused_round`."""

    assignment: jax.Array     # (N,) int32
    barycenters: jax.Array    # (K, D) float32
    counts: jax.Array         # (K,) float32
    new_center_idx: jax.Array # (K,) int32 medoid centers v_j^{r+1}
    theta: jax.Array          # (D,) float32
    radius: jax.Array         # (K,) float32 RMS member->barycenter distance
    med_d2: jax.Array         # (N, K) float32 client->barycenter sq dists
                              # (sketch-space under a sketcher, like radius)


#: Every geometry matmul runs at full f32 precision.  A default-precision f32
#: dot on the TPU rounds its operands to bf16: the Gram form
#: ‖w‖² + ‖c‖² − 2⟨w, c⟩ then cancels catastrophically (clients start each
#: round from one θ, so their distances are tiny next to their norms), and
#: barycenters keep ~3 significant digits.  The CPU computes f32 dots exactly
#: either way, so this changes no CPU result.
mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


# --- sweep chunk size ------------------------------------------------------------

#: cap on the streaming sweep tile: (N, 64k) f32 chunks keep the resident
#: working set a few MB at federation-scale N while amortising slice overhead.
DEFAULT_CHUNK = 65536


def default_chunk(d: int) -> int:
    """Size-derived sweep chunk for a D-wide weight matrix.

    Models narrower than the cap stream as one exact tile (no padded tail,
    no scan); wider ones use the :data:`DEFAULT_CHUNK` cap.  Padding columns
    are zeros, so either choice is bit-for-bit identical — the knob only
    moves compute/memory, never numerics (sums of nonnegative terms gain
    trailing ``+0.0`` at most).
    """
    return max(1, min(int(d), DEFAULT_CHUNK))


def resolve_chunk(chunk: int | None, d: int) -> int:
    """``chunk`` if explicitly set (validated), else :func:`default_chunk`."""
    if chunk is None:
        return default_chunk(d)
    chunk = int(chunk)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    return chunk


# --- shared glue (the O(N*K) algebra between the two passes) ---------------------

def pin_assignment(d2_centers: jax.Array, center_idx: jax.Array) -> jax.Array:
    """Nearest-center argmin with centers pinned to their own coalition.

    Identical math to :func:`repro.core.coalitions.assign` — factored out so
    every fused backend shares one pinning rule.
    """
    n, k = d2_centers.shape
    a = jnp.argmin(d2_centers, axis=1).astype(jnp.int32)
    pin = jnp.full((n,), -1, jnp.int32).at[center_idx].set(
        jnp.arange(k, dtype=jnp.int32))
    return jnp.where(pin >= 0, pin, a)


def aggregation_matrix(assignment: jax.Array, k: int, center_idx: jax.Array,
                       client_weights: jax.Array | None = None,
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Weighted membership matrix with the empty-coalition fallback folded in.

    Returns ``(oh_eff, counts, denom)``: a (K, N) matrix whose row j is the
    (client-weighted) membership indicator of coalition j — or, when the
    coalition's mass is zero, the indicator of its previous center with unit
    mass — plus the pre-fallback masses and the barycenter denominators.
    ``oh_eff @ W / denom[:, None]`` is then the complete barycenter step,
    fallback included, as a single matmul.
    """
    n = assignment.shape[0]
    onehot = jax.nn.one_hot(assignment, k, dtype=jnp.float32).T      # (K, N)
    if client_weights is not None:
        onehot = onehot * client_weights.astype(jnp.float32)[None, :]
    counts = jnp.sum(onehot, axis=1)                                 # (K,)
    empty = counts == 0.0
    fallback_rows = jax.nn.one_hot(center_idx, n, dtype=jnp.float32)  # (K, N)
    oh_eff = jnp.where(empty[:, None], fallback_rows, onehot)
    # Same clamp as barycenter.barycenters: far below any real fractional
    # mass, only dodging 0/0 (which the fallback substitution already avoids).
    denom = jnp.where(empty, 1.0, jnp.maximum(counts, 1e-12))
    return oh_eff, counts, denom


#: Members within this relative margin of the smallest distance to their
#: barycenter tie, and the lowest client index among them is elected.  Exact
#: ties are structural (both members of an equal-weight two-client coalition
#: sit at the same distance from their midpoint), and f32 roundoff, which
#: differs between backends, chunk widths and mesh shards, must not decide
#: them.  The margin is far above that roundoff (~1e-6 relative).
MEDOID_TIE_RTOL = 1e-4


def medoid_from_d2(med_d2: jax.Array, assignment: jax.Array,
                   client_weights: jax.Array | None = None) -> jax.Array:
    """Step III center update from accumulated client->barycenter distances.

    Restricted to members of each coalition; zero-mass clients (participation
    mask 0 under ``semi_async``) are not electable — a center that contributed
    nothing to the barycenter must not anchor next round's assignment.  Near
    ties go to the lowest index (:data:`MEDOID_TIE_RTOL`).  Falls
    back to the global argmin when a coalition has no positive-mass member so
    the returned index stays valid.
    """
    k = med_d2.shape[1]
    member = assignment[:, None] == jnp.arange(k)[None, :]           # (N, K)
    if client_weights is not None:
        member = member & (client_weights > 0)[:, None]
    masked = jnp.where(member, med_d2, jnp.inf)
    best = jnp.min(masked, axis=0)                                   # (K,)
    tied = masked <= best * (1.0 + MEDOID_TIE_RTOL)
    any_member = jnp.any(member, axis=0)
    idx = jnp.where(any_member, jnp.argmax(tied, axis=0),
                    jnp.argmin(med_d2, axis=0))
    return idx.astype(jnp.int32)


# --- xla: lax.scan streaming composition ----------------------------------------

def _zeros_like_rows(w: jax.Array, k: int) -> jax.Array:
    """(N, k) f32 zeros that inherit ``w``'s mesh-varying axes.

    Under ``shard_map`` a scan carry must vary over the same mesh axes as the
    body's output; deriving the initial accumulator from the (D-sharded) W
    tile makes it so, and outside ``shard_map`` it is plain zeros.
    """
    return jnp.zeros_like(w, shape=(w.shape[0], k), dtype=jnp.float32)


def _xla_center_d2(w: jax.Array, center_idx: jax.Array, chunk: int) -> jax.Array:
    """Pass 1: (N, K) assignment distances, center rows read out of each chunk.

    Chunk partition, padding, and accumulation order mirror
    ``distance._to_points_sq_xla`` exactly so the result is bit-for-bit equal
    to the composed path — but W is sliced in place (``dynamic_slice``), never
    transposed or re-materialised, and the (K, D) center gather never exists.
    """
    d = w.shape[1]
    k = center_idx.shape[0]
    nfull, tail = divmod(d, chunk)

    def accum(acc, wk):
        pk = wk[center_idx]                                  # (K, c) in-chunk
        diff = wk[:, None, :] - pk[None, :, :]
        return acc + jnp.sum(diff * diff, axis=-1)

    acc = _zeros_like_rows(w, k)
    if nfull:
        def body(carry, i):
            wk = jax.lax.dynamic_slice_in_dim(
                w, i * chunk, chunk, 1).astype(jnp.float32)
            return accum(carry, wk), None

        acc, _ = jax.lax.scan(body, acc, jnp.arange(nfull))
    if tail:
        wk = jnp.pad(w[:, nfull * chunk:].astype(jnp.float32),
                     ((0, 0), (0, chunk - tail)))
        acc = accum(acc, wk)
    return acc


def _xla_bary_med_theta(w: jax.Array, oh_eff: jax.Array, denom: jax.Array,
                        chunk: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pass 2: barycenters + θ tiles emitted per chunk, medoid d² accumulated."""
    d = w.shape[1]
    k = oh_eff.shape[0]
    nfull, tail = divmod(d, chunk)

    def emit(acc, wk):
        bc = mm(oh_eff, wk) / denom[:, None]                 # (K, c)
        tc = jnp.mean(bc, axis=0)                            # (c,)
        diff = wk[:, None, :] - bc[None, :, :]
        return acc + jnp.sum(diff * diff, axis=-1), bc, tc

    acc = _zeros_like_rows(w, k)
    b_parts, t_parts = [], []
    if nfull:
        def body(carry, i):
            wk = jax.lax.dynamic_slice_in_dim(
                w, i * chunk, chunk, 1).astype(jnp.float32)
            carry, bc, tc = emit(carry, wk)
            return carry, (bc, tc)

        acc, (bcs, tcs) = jax.lax.scan(body, acc, jnp.arange(nfull))
        b_parts.append(jnp.moveaxis(bcs, 0, 1).reshape(k, nfull * chunk))
        t_parts.append(tcs.reshape(nfull * chunk))
    if tail:
        wk = jnp.pad(w[:, nfull * chunk:].astype(jnp.float32),
                     ((0, 0), (0, chunk - tail)))
        acc, bc, tc = emit(acc, wk)
        b_parts.append(bc[:, :tail])
        t_parts.append(tc[:tail])
    b = b_parts[0] if len(b_parts) == 1 else jnp.concatenate(b_parts, axis=1)
    theta = t_parts[0] if len(t_parts) == 1 else jnp.concatenate(t_parts)
    return b, theta, acc


def _xla_bary_theta(w: jax.Array, oh_eff: jax.Array, denom: jax.Array,
                    chunk: int) -> tuple[jax.Array, jax.Array]:
    """Barycenter + θ tiles only — the sketched round's pass 2.

    Same chunking/association as :func:`_xla_bary_med_theta` minus the medoid
    accumulator (the sketched round elects medoids in sketch space, so the
    (N, K) diff-square work would be dead compute).
    """
    n, d = w.shape
    k = oh_eff.shape[0]
    nfull, tail = divmod(d, chunk)

    def emit(wk):
        bc = mm(oh_eff, wk) / denom[:, None]                 # (K, c)
        return bc, jnp.mean(bc, axis=0)

    b_parts, t_parts = [], []
    if nfull:
        def body(carry, i):
            wk = jax.lax.dynamic_slice_in_dim(
                w, i * chunk, chunk, 1).astype(jnp.float32)
            return carry, emit(wk)

        _, (bcs, tcs) = jax.lax.scan(body, None, jnp.arange(nfull))
        b_parts.append(jnp.moveaxis(bcs, 0, 1).reshape(k, nfull * chunk))
        t_parts.append(tcs.reshape(nfull * chunk))
    if tail:
        wk = jnp.pad(w[:, nfull * chunk:].astype(jnp.float32),
                     ((0, 0), (0, chunk - tail)))
        bc, tc = emit(wk)
        b_parts.append(bc[:, :tail])
        t_parts.append(tc[:tail])
    b = b_parts[0] if len(b_parts) == 1 else jnp.concatenate(b_parts, axis=1)
    theta = t_parts[0] if len(t_parts) == 1 else jnp.concatenate(t_parts)
    return b, theta


def fused_round_xla(w: jax.Array, center_idx: jax.Array, *,
                    client_weights: jax.Array | None = None,
                    chunk: int | None = None, **_) -> FusedStats:
    """The exact streaming reference: two ``lax.scan`` sweeps over W."""
    k = center_idx.shape[0]
    chunk = resolve_chunk(chunk, w.shape[1])
    instrument.count_w_pass()                                # pass 1
    d2c = _xla_center_d2(w, center_idx, chunk)
    assignment = pin_assignment(d2c, center_idx)
    oh_eff, counts, denom = aggregation_matrix(assignment, k, center_idx,
                                               client_weights)
    instrument.count_w_pass()                                # pass 2
    b, theta, med_d2 = _xla_bary_med_theta(w, oh_eff, denom, chunk)
    return FusedStats(assignment=assignment, barycenters=b, counts=counts,
                      med_d2=med_d2, theta=theta)


# --- dot: Gram composition -------------------------------------------------------

def centered_gram(wf: jax.Array) -> jax.Array:
    """(N, N) Gram matrix of the rows of ``wf`` less their column mean.

    Distances are translation-invariant, and centered rows are as small as
    the distances between them: clients that start a round from one θ sit
    close together far from the origin, and the uncentered Gram form
    ‖w_i‖² + ‖w_j‖² − 2⟨w_i, w_j⟩ would cancel away most of f32's digits.
    """
    wc = wf - jnp.mean(wf, axis=0, keepdims=True)
    return mm(wc, wc.T)


def fused_round_dot(w: jax.Array, center_idx: jax.Array, *,
                    client_weights: jax.Array | None = None, **_) -> FusedStats:
    """Gram form: with W sharded over D the pass-1 contraction shrinks to an
    (N, N) all-reduce, and the medoid distances are pure Gram algebra —
    ⟨w_i, b_j⟩ = (G · M^T)_ij / denom_j — so only the segment matmul (pass 2)
    re-reads W.  G is the :func:`centered_gram`; each row of M / denom sums
    to one, so the barycenters it implies are centered by the same mean."""
    k = center_idx.shape[0]
    wf = w.astype(jnp.float32)
    instrument.count_w_pass()                                # pass 1
    gram = centered_gram(wf)                                 # (N, N)
    sq = jnp.diagonal(gram)                                  # ‖w_i − w̄‖²
    d2c = jnp.maximum(sq[:, None] + sq[center_idx][None, :]
                      - 2.0 * gram[:, center_idx], 0.0)
    assignment = pin_assignment(d2c, center_idx)
    oh_eff, counts, denom = aggregation_matrix(assignment, k, center_idx,
                                               client_weights)
    instrument.count_w_pass()                                # pass 2
    b = mm(oh_eff, wf) / denom[:, None]
    theta = jnp.mean(b, axis=0)
    cross = mm(gram, oh_eff.T) / denom[None, :]              # (N, K) ⟨w_i, b_j⟩
    bsq = jnp.diagonal(mm(mm(oh_eff, gram), oh_eff.T)) / (denom * denom)
    med_d2 = jnp.maximum(sq[:, None] + bsq[None, :] - 2.0 * cross, 0.0)
    return FusedStats(assignment=assignment, barycenters=b, counts=counts,
                      med_d2=med_d2, theta=theta)


# --- pallas: TPU kernels ---------------------------------------------------------

def fused_round_pallas(w: jax.Array, center_idx: jax.Array, *,
                       client_weights: jax.Array | None = None,
                       block_d: int = 16384, **_) -> FusedStats:
    """Route both passes through the :mod:`repro.kernels.fused_round` kernels
    (lazy import so a missing TPU toolchain never breaks CPU-only use)."""
    from repro.kernels import ops as kops

    n = w.shape[0]
    k = center_idx.shape[0]
    conehot = jax.nn.one_hot(center_idx, n, dtype=jnp.float32)   # (K, N)
    instrument.count_w_pass()                                # pass 1
    d2c = kops.center_sq_dists(w, conehot, block_d=block_d)
    assignment = pin_assignment(d2c, center_idx)
    oh_eff, counts, denom = aggregation_matrix(assignment, k, center_idx,
                                               client_weights)
    instrument.count_w_pass()                                # pass 2
    b, theta, med_d2 = kops.fused_coalition_stats(
        w, oh_eff / denom[:, None], block_d=block_d)
    return FusedStats(assignment=assignment, barycenters=b, counts=counts,
                      med_d2=med_d2, theta=theta)


# --- generic fall-back composition ----------------------------------------------

def compose_fused_round(backend: bk.Backend, w: jax.Array,
                        center_idx: jax.Array, *,
                        client_weights: jax.Array | None = None,
                        **kw) -> FusedStats:
    """Build the round from the three base primitives only.

    Third-party backends registered before ``Backend.fused_round`` existed
    (``fused_round=None``) still serve every coalition strategy through this
    composition: one center gather plus three primitive calls, with the
    fallback folded into the segment-sum matrix.  Division happens after the
    reduction and θ after the division — the same association order as the
    streaming implementations — so a backend wrapping the xla primitives
    stays bit-for-bit equal to the fused xla path.
    """
    k = center_idx.shape[0]
    centers = jnp.take(w, center_idx, axis=0)
    d2c = backend.sq_dists_to_points(w, centers, **kw)
    assignment = pin_assignment(d2c, center_idx)
    oh_eff, counts, denom = aggregation_matrix(assignment, k, center_idx,
                                               client_weights)
    b = backend.segment_sum(oh_eff, w, **kw) / denom[:, None]
    theta = jnp.mean(b, axis=0)
    med_d2 = backend.sq_dists_to_points(w, b, **kw)
    return FusedStats(assignment=assignment, barycenters=b, counts=counts,
                      med_d2=med_d2, theta=theta)


# --- sketched round (assignment + medoids in sketch space) ------------------------

def sketch_stage(backend: bk.Backend, s_w: jax.Array, center_idx: jax.Array, *,
                 client_weights: jax.Array | None = None):
    """Pass 1 + medoid geometry entirely on the (N, S) sketch.

    Because the sketch map is linear, sketched barycenters are exact sketches
    of the true barycenters: ``S(Σαᵢωᵢ/m) = (oh_eff @ S_w) / denom`` — so the
    client→barycenter distances that elect medoids (and the intra radius) are
    plain JL estimates, and nothing here ever touches full W.  The backend's
    own distance primitives run on the sketch under
    :func:`instrument.suspend_w_passes` (an S-wide sweep is not a W pass).

    Returns ``(assignment, oh_eff, counts, denom, med_d2)``.
    """
    k = center_idx.shape[0]
    with instrument.suspend_w_passes():
        centers = jnp.take(s_w, center_idx, axis=0)
        d2c = backend.sq_dists_to_points(s_w, centers)
        assignment = pin_assignment(d2c, center_idx)
        oh_eff, counts, denom = aggregation_matrix(assignment, k, center_idx,
                                                   client_weights)
        s_b = mm(oh_eff, s_w.astype(jnp.float32)) / denom[:, None]   # (K, S)
        med_d2 = backend.sq_dists_to_points(s_w, s_b)
    return assignment, oh_eff, counts, denom, med_d2


def sketched_fused_round(backend: bk.Backend, w: jax.Array, s_w: jax.Array,
                         center_idx: jax.Array, *,
                         client_weights: jax.Array | None = None,
                         **kw) -> FusedStats:
    """One coalition round given a precomputed sketch: ONE full sweep over W.

    The classic two-pass structure collapses: assignment distances AND the
    medoid-electing distances come from ``s_w``; the only full-W traffic left
    is the barycenter segment matmul (which self-counts its single pass).
    With the sketch construction itself (one more sweep) the complete
    sketched round costs ≤ 2 full sweeps — never more than the exact fused
    round, and the sweep that remains is a pure matmul.
    """
    assignment, oh_eff, counts, denom, med_d2 = sketch_stage(
        backend, s_w, center_idx, client_weights=client_weights)
    b = backend.segment_sum(oh_eff, w, **kw) / denom[:, None]
    theta = jnp.mean(b, axis=0)
    return FusedStats(assignment=assignment, barycenters=b, counts=counts,
                      med_d2=med_d2, theta=theta)


# --- dispatcher ------------------------------------------------------------------

def fused_round(w: jax.Array, center_idx: jax.Array, *,
                client_weights: jax.Array | None = None,
                backend: str | bk.Backend = "xla",
                sketcher: sk_mod.Sketcher | None = None, **kw) -> FusedRound:
    """One fused Algorithm-1 round (Steps II-IV) over client weights ``w``.

    Resolves ``backend.fused_round`` when the backend provides it, else the
    generic :func:`compose_fused_round`; finishes with the shared medoid
    argmin (zero-mass clients excluded — see :func:`medoid_from_d2`).

    The per-coalition intra radius rides along for free: it is O(N·K)
    algebra over the same accumulated ``med_d2`` that elects the medoids, so
    the trace-time W-pass count stays exactly 2 (tested).

    A non-identity ``sketcher`` reroutes pass 1 and the medoid election to
    the (N, S) sketch (see :func:`sketched_fused_round`): ≤ 2 full sweeps
    total, exactly 1 once the sketch is in hand.  Sharded backends provide
    their own ``sketched_fused_round`` (partial sketches psum along the mesh
    axis); every other backend sketches densely and shares one route.
    """
    backend = bk.get_backend(backend)
    if sketcher is not None and not sketcher.is_identity:
        if backend.sketched_fused_round is not None:
            s = backend.sketched_fused_round(
                w, center_idx, client_weights=client_weights,
                sketcher=sketcher, **kw)
        else:
            s_w = sk_mod.sketch_matrix(sketcher, w)
            s = sketched_fused_round(backend, w, s_w, center_idx,
                                     client_weights=client_weights, **kw)
    else:
        impl = (backend.fused_round if backend.fused_round is not None
                else functools.partial(compose_fused_round, backend))
        s = impl(w, center_idx, client_weights=client_weights, **kw)
    new_center_idx = medoid_from_d2(s.med_d2, s.assignment, client_weights)
    radius = obs_metrics.intra_radius(s.med_d2, s.assignment,
                                      center_idx.shape[0], client_weights)
    return FusedRound(assignment=s.assignment, barycenters=s.barycenters,
                      counts=s.counts, new_center_idx=new_center_idx,
                      theta=s.theta, radius=radius, med_d2=s.med_d2)
