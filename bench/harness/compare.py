"""The numbers that decide ``correct``, each against its limit.

A training cell compares the warm-up federation run, the first call of the
timed object, with the plain reference (``harness.reference.follow``) over
its round-0 prologue and its first scanned chunk, up to the round ``e`` at
the chunk's end, where the program publishes its second snapshot.  The
numbers it can read:

``loss0``    the relative gap of round 0's mean client loss;
``loss_end`` the same at round ``e``, the last round of the first chunk;
``wmean0``   round 0's mean client weights (the counts-weighted mean of the
             published barycenters) less θ's start, by the worst leaf: the
             gap between the program's and the reference's norm of that
             leaf's change, over the reference's norm of it or of the
             median leaf, whichever is larger;
``update0``  round 0's change of θ, by the worst leaf in the same measure;
``wmean_end``, ``update_end``  the same at round ``e``: the change over
             round 0 and the whole scanned chunk, read from the snapshot
             that the chunk's carry published;
``assign0``  how many of round 0's clear memberships differ: those of the
             clients whose two nearest centers are at least ``CLEAR`` apart
             (relatively) in the reference (``clear0`` counts them).

Which of them decide ``correct`` is the cell's limits file; ``PERF.md``
gives the readings each limit was set from and why the others are not
compared.
"""
from __future__ import annotations

import jax
import numpy as np

#: a client whose nearest and second-nearest centers lie this far apart,
#: relatively, in the reference has a clear assignment: rounding cannot
#: move it to another coalition
CLEAR = 0.05
#: a leaf whose reference change is under this share of the median leaf's
#: moves by round-off alone and is left out of the leaf comparison
NOUGHT = 1e-3


def norm_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Worst |‖p‖ − ‖r‖| / max(‖r‖, median ‖r‖) over matched norms."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    med = float(np.median(ref))
    keep = ref >= NOUGHT * med
    if not keep.any():
        return 0.0
    denom = np.maximum(ref[keep], med)
    return float(np.max(np.abs(prog[keep] - ref[keep]) / denom))


def leaf_norms(tree: dict, start: dict) -> np.ndarray:
    return np.asarray([np.linalg.norm(np.asarray(tree[n][k], np.float64)
                                      - np.asarray(start[n][k], np.float64))
                       for n in sorted(tree) for k in sorted(tree[n])])


def train_numbers(prog: dict, ref: dict, start: dict,
                  end: int) -> dict[str, float]:
    """``prog`` and ``ref`` hold per-round ``loss`` and ``assignment`` and,
    for rounds 0 and ``end``, ``theta[r]`` and ``wmean[r]`` (the clients'
    mean weights); ``ref`` also round 0's ``margin``.  ``start`` is θ before
    round 0."""
    def loss_gap(r):
        lp, lr = float(prog["loss"][r]), float(ref["loss"][r])
        return abs(lp - lr) / abs(lr)

    def leaf_gap(what, r):
        return norm_gap(leaf_norms(prog[what][r], start),
                        leaf_norms(ref[what][r], start))

    clear = np.asarray(ref["margin"][0]) >= CLEAR
    assign0 = int(np.sum((np.asarray(prog["assignment"][0])
                          != np.asarray(ref["assignment"][0]))[clear]))
    return {"loss0": loss_gap(0), "loss_end": loss_gap(end),
            "wmean0": leaf_gap("wmean", 0), "update0": leaf_gap("theta", 0),
            "wmean_end": leaf_gap("wmean", end),
            "update_end": leaf_gap("theta", end),
            "assign0": float(assign0), "clear0": float(np.sum(clear))}


def mean_of_members(barycenters: np.ndarray, counts: np.ndarray,
                    like: dict) -> dict:
    """The clients' mean weights, Σ_k counts_k · b_k / Σ counts, split into
    the leaves of ``like`` in its flattening order (the serving store's
    layout of a barycenter row)."""
    b = np.asarray(barycenters, np.float64)
    c = np.asarray(counts, np.float64)
    vec = (c[:, None] * b).sum(axis=0) / c.sum()
    leaves, treedef = jax.tree.flatten(like)
    out, i = [], 0
    for leaf in leaves:
        out.append(vec[i:i + leaf.size].reshape(leaf.shape))
        i += leaf.size
    return jax.tree.unflatten(treedef, out)


def judge(numbers: dict[str, float], limits: dict[str, float]) -> dict:
    """``{name: {"value", "limit"}}`` for every limited number; a number
    without a limit is an error, so that no comparison goes unchecked."""
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise KeyError(f"no reading for the limited numbers {missing}")
    return {name: {"value": numbers[name], "limit": limits[name]}
            for name in limits}


def passed(checks: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
