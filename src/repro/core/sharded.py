"""Mesh-parallel fused round: ``shard_map`` over the ``data`` axis.

The cohort's (C, D) weight matrix is sharded along **D** — each mesh device
owns a (C, D/p) tile — and the two-pass fused round runs per shard with two
``psum`` all-reduces stitching the passes together:

  pass 1 — every shard accumulates its *partial* (C, K) center distances
           (or, on the ``dot`` backend, its partial (C, C) Gram tile) from
           its local columns; one ``psum`` of that small matrix yields the
           full distances.  Assignment, the aggregation matrix, and the
           empty-coalition fallback are then O(C·K) replicated algebra —
           identical on every shard.
  pass 2 — every shard computes its *local tile* of the barycenters
           ``(K, D/p)`` and of θ ``(D/p,)`` (these stay sharded — no
           all-gather of model-sized data, matching the levanter/maxtext
           idiom), plus its partial medoid distances; the second ``psum``
           completes the (C, K) medoid matrix that elects next round's
           centers.

Each shard reads its W tile **exactly twice** — the trace-time two-pass
invariant holds per shard (``instrument`` counting works inside
``shard_map`` because it fires at trace time) — and the collectives move
O(C²) floats per round, never O(D).

On a 1-device mesh every ``psum`` is a sum over one term, so the sharded
round is bit-for-bit identical to the dense path (asserted in
tests/test_sharded.py); on p > 1 devices the per-shard chunk partition
changes summation boundaries and parity is allclose-level instead.

D is zero-padded up to a multiple of the mesh axis; zero columns are exact
no-ops in every reduction (squared diffs and Gram products of zeros), and
the pad is sliced back off outside the ``shard_map``.

Entry point: :func:`sharded_backend` wraps a registered base backend
(``xla`` | ``dot`` | ``pallas``) into a new :class:`~repro.core.backends.
Backend` whose ``fused_round`` is the mesh-parallel version.  The three
base primitives pass through unchanged, so the composed path and
``init_centers`` keep working (dense, replicated) on the wrapped backend.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import backends as bk
from repro.core import fused as fz
from repro.core import instrument
from repro.core import sketch as sk_mod


def _finish_pass1(d2c, center_idx, client_weights):
    """The replicated O(C·K) algebra between the two passes."""
    k = center_idx.shape[0]
    assignment = fz.pin_assignment(d2c, center_idx)
    oh_eff, counts, denom = fz.aggregation_matrix(assignment, k, center_idx,
                                                  client_weights)
    return assignment, oh_eff, counts, denom


def _local_xla(w_loc, center_idx, client_weights, *, chunk, axis):
    """Streaming sweeps over the local (C, D/p) tile, psum-stitched."""
    instrument.count_w_pass()                                # pass 1 (local)
    d2c = jax.lax.psum(fz._xla_center_d2(w_loc, center_idx, chunk), axis)
    assignment, oh_eff, counts, denom = _finish_pass1(
        d2c, center_idx, client_weights)
    instrument.count_w_pass()                                # pass 2 (local)
    b, theta, med_part = fz._xla_bary_med_theta(w_loc, oh_eff, denom, chunk)
    med_d2 = jax.lax.psum(med_part, axis)
    return fz.FusedStats(assignment=assignment, barycenters=b, counts=counts,
                         med_d2=med_d2, theta=theta)


def _local_dot(w_loc, center_idx, client_weights, *, chunk, axis):
    """Gram form: the pass-1 collective is the (C, C) partial-Gram psum —
    exactly the D-sharding this backend was built for.  Each tile centers
    its own columns; the mean is per column, so the psum of the tiles'
    centered Grams is the whole centered Gram."""
    instrument.count_w_pass()                                # pass 1 (local)
    wf = w_loc.astype(jnp.float32)
    gram = jax.lax.psum(fz.centered_gram(wf), axis)          # (C, C)
    sq = jnp.diagonal(gram)
    d2c = jnp.maximum(sq[:, None] + sq[center_idx][None, :]
                      - 2.0 * gram[:, center_idx], 0.0)
    assignment, oh_eff, counts, denom = _finish_pass1(
        d2c, center_idx, client_weights)
    instrument.count_w_pass()                                # pass 2 (local)
    b = fz.mm(oh_eff, wf) / denom[:, None]                   # (K, D/p) tile
    theta = jnp.mean(b, axis=0)                              # (D/p,) tile
    cross = fz.mm(gram, oh_eff.T) / denom[None, :]
    bsq = jnp.diagonal(fz.mm(fz.mm(oh_eff, gram), oh_eff.T)) / (denom * denom)
    med_d2 = jnp.maximum(sq[:, None] + bsq[None, :] - 2.0 * cross, 0.0)
    return fz.FusedStats(assignment=assignment, barycenters=b, counts=counts,
                         med_d2=med_d2, theta=theta)


def _local_pallas(w_loc, center_idx, client_weights, *, chunk, axis):
    """Both passes through the :mod:`repro.kernels` tiles, per shard."""
    from repro.kernels import ops as kops

    n = w_loc.shape[0]
    conehot = jax.nn.one_hot(center_idx, n, dtype=jnp.float32)
    instrument.count_w_pass()                                # pass 1 (local)
    d2c = jax.lax.psum(kops.center_sq_dists(w_loc, conehot), axis)
    assignment, oh_eff, counts, denom = _finish_pass1(
        d2c, center_idx, client_weights)
    instrument.count_w_pass()                                # pass 2 (local)
    b, theta, med_part = kops.fused_coalition_stats(
        w_loc, oh_eff / denom[:, None])
    med_d2 = jax.lax.psum(med_part, axis)
    return fz.FusedStats(assignment=assignment, barycenters=b, counts=counts,
                         med_d2=med_d2, theta=theta)


_LOCALS = {"xla": _local_xla, "dot": _local_dot, "pallas": _local_pallas}

#: The pallas body runs with the varying-axes checker off: a checked
#: ``shard_map`` needs every pallas_call output typed with its ``vma``, and
#: the kernel bodies then still mix the replicated (K, N) operand with the
#: mesh-varying W tile in one dot, which the checker rejects (jax 0.9).  Its
#: P() outputs are still genuinely replicated (they come out of the same
#: psums as the xla body).
_UNCHECKED = frozenset({"pallas"})

#: specs of a FusedStats coming out of the per-shard body: assignment /
#: counts / med_d2 are psum-derived (replicated), barycenter and θ tiles
#: stay D-sharded along the mesh axis.
def stats_specs(axis: str) -> fz.FusedStats:
    return fz.FusedStats(assignment=P(), barycenters=P(None, axis),
                         counts=P(), med_d2=P(), theta=P(axis))


# --- sketched round: psum partial sketches, one local bary/θ sweep ---------------

def _pass2_xla(w_loc, oh_eff, denom, *, chunk):
    return fz._xla_bary_theta(w_loc, oh_eff, denom, chunk)


def _pass2_dot(w_loc, oh_eff, denom, *, chunk):
    b = fz.mm(oh_eff, w_loc.astype(jnp.float32)) / denom[:, None]
    return b, jnp.mean(b, axis=0)


def _pass2_pallas(w_loc, oh_eff, denom, *, chunk):
    from repro.kernels import ops as kops

    b = kops.segment_sum(oh_eff, w_loc) / denom[:, None]
    return b, jnp.mean(b, axis=0)


_SKETCH_PASS2 = {"xla": _pass2_xla, "dot": _pass2_dot, "pallas": _pass2_pallas}


def _sq_to_points(x, p):
    """Small replicated (C, K) sketch-space distances (diff-square form)."""
    diff = x[:, None, :] - p[None, :, :]
    return jnp.sum(diff * diff, axis=-1)


def _local_sketched(pass2, sketcher, w_loc, center_idx, client_weights, *,
                    chunk, axis):
    """Per-shard sketched round: each shard reads its W tile exactly twice —
    once to build its partial sketch (psum-stitched into the replicated
    (C, S) sketch), once for its barycenter/θ tiles.  Assignment, medoid
    election, and the intra radius are replicated sketch-space algebra, so
    the only collectives are the (C, S) sketch psum — still never O(D)."""
    instrument.count_w_pass()                    # sketch sweep (local tile)
    off = jax.lax.axis_index(axis) * w_loc.shape[1]
    s_w = jax.lax.psum(sk_mod.sketch_block(sketcher, w_loc, col_offset=off),
                       axis)
    d2c = _sq_to_points(s_w, s_w[center_idx])
    assignment, oh_eff, counts, denom = _finish_pass1(
        d2c, center_idx, client_weights)
    s_b = fz.mm(oh_eff, s_w) / denom[:, None]                # (K, S)
    med_d2 = _sq_to_points(s_w, s_b)
    instrument.count_w_pass()                    # bary/θ sweep (local tile)
    b, theta = pass2(w_loc, oh_eff, denom, chunk=chunk)
    return fz.FusedStats(assignment=assignment, barycenters=b, counts=counts,
                         med_d2=med_d2, theta=theta)


def _sharded_sketched_round(base_name, mesh, axis, check, w, center_idx, *,
                            sketcher, client_weights=None, chunk=None, **_):
    parts = mesh.shape[axis]
    n, d = w.shape
    pad = (-d) % parts
    wp = jnp.pad(w, ((0, 0), (0, pad))) if pad else w
    body = partial(_local_sketched, _SKETCH_PASS2[base_name], sketcher,
                   chunk=fz.resolve_chunk(chunk, (d + pad) // parts),
                   axis=axis)
    out_specs = stats_specs(axis)
    if client_weights is None:
        f = jax.shard_map(lambda wl, ci: body(wl, ci, None), mesh=mesh,
                      in_specs=(P(None, axis), P()), out_specs=out_specs,
                      check_vma=check)
        s = f(wp, center_idx)
    else:
        f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(None, axis), P(), P()), out_specs=out_specs,
                      check_vma=check)
        s = f(wp, center_idx, client_weights)
    if pad:
        s = s._replace(barycenters=s.barycenters[:, :d], theta=s.theta[:d])
    return s


def _sharded_fused_round(local, mesh, axis, check, w, center_idx, *,
                         client_weights=None, chunk=None, **_):
    parts = mesh.shape[axis]
    n, d = w.shape
    pad = (-d) % parts
    wp = jnp.pad(w, ((0, 0), (0, pad))) if pad else w
    body = partial(local, chunk=fz.resolve_chunk(chunk, (d + pad) // parts),
                   axis=axis)
    out_specs = stats_specs(axis)
    if client_weights is None:
        f = jax.shard_map(lambda wl, ci: body(wl, ci, None), mesh=mesh,
                      in_specs=(P(None, axis), P()), out_specs=out_specs,
                      check_vma=check)
        s = f(wp, center_idx)
    else:
        f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(None, axis), P(), P()), out_specs=out_specs,
                      check_vma=check)
        s = f(wp, center_idx, client_weights)
    if pad:
        s = s._replace(barycenters=s.barycenters[:, :d], theta=s.theta[:d])
    return s


def sharded_backend(base: str | bk.Backend, mesh, *,
                    axis: str = "data") -> bk.Backend:
    """Wrap a registered backend's fused round in a mesh-parallel one.

    ``mesh`` is a :class:`jax.sharding.Mesh` with an ``axis`` dimension (from
    :func:`repro.launch.mesh.make_host_mesh` / ``parse_mesh``).  The returned
    backend is a drop-in for strategy construction; its name records the
    sharding (``"xla@data8"``) so run metadata stays self-describing.
    """
    base = bk.get_backend(base)
    if base.name not in _LOCALS:
        raise ValueError(
            f"no sharded fused round for backend {base.name!r} "
            f"(choose from {sorted(_LOCALS)})")
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no {axis!r} axis (axes: {mesh.axis_names})")
    check = base.name not in _UNCHECKED
    impl = partial(_sharded_fused_round, _LOCALS[base.name], mesh, axis, check)
    sk_impl = partial(_sharded_sketched_round, base.name, mesh, axis, check)
    return base._replace(name=f"{base.name}@{axis}{mesh.shape[axis]}",
                         fused_round=impl, sketched_fused_round=sk_impl)
