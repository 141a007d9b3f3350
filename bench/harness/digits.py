"""Synthetic MNIST-shaped digits, generated from a seed.

A copy of the program's seven-segment digit generator, kept here so that
the benchmark's inputs cannot move when the program's data code changes:
10 classes of 28x28 grayscale glyphs with per-sample shifts, stroke
intensity and Gaussian pixel noise.
"""
from __future__ import annotations

import numpy as np

#   A
#  F B
#   G
#  E C
#   D
_SEGMENTS = {
    0: "ABCDEF", 1: "BC", 2: "ABGED", 3: "ABGCD", 4: "FGBC",
    5: "AFGCD", 6: "AFGECD", 7: "ABC", 8: "ABCDEFG", 9: "ABCFGD",
}
# segment -> (row0, col0, row1, col1) in a 24x14 glyph box
_SEG_COORDS = {
    "A": (1, 2, 1, 11), "B": (2, 11, 10, 11), "C": (13, 11, 21, 11),
    "D": (22, 2, 22, 11), "E": (13, 2, 21, 2), "F": (2, 2, 10, 2),
    "G": (11, 2, 11, 11),
}


def _render(digit: int) -> np.ndarray:
    img = np.zeros((28, 28), np.float32)
    for seg in _SEGMENTS[digit]:
        r0, c0, r1, c1 = _SEG_COORDS[seg]
        npts = max(abs(r1 - r0), abs(c1 - c0)) + 1
        rs = np.linspace(r0, r1, npts).round().astype(int) + 2
        cs = np.linspace(c0, c1, npts).round().astype(int) + 7
        for rr, cc in zip(rs, cs):
            img[max(rr - 1, 0):rr + 2, max(cc - 1, 0):cc + 2] = 1.0
    return img


TEMPLATES = np.stack([_render(d) for d in range(10)])


def digits(n: int, seed: int, noise: float = 0.25,
           max_shift: int = 3) -> tuple[np.ndarray, np.ndarray]:
    """``n`` digits: x float32 (n, 28, 28, 1) in [0, 1], y int32 (n,)."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 10, size=n).astype(np.int32)
    t = TEMPLATES[y]
    sr = rng.integers(-max_shift, max_shift + 1, size=n)
    sc = rng.integers(-max_shift, max_shift + 1, size=n)
    x = np.zeros_like(t)
    for i in range(n):
        x[i] = np.roll(np.roll(t[i], sr[i], axis=0), sc[i], axis=1)
    x *= rng.uniform(0.6, 1.0, size=(n, 1, 1)).astype(np.float32)
    x += noise * rng.standard_normal(x.shape).astype(np.float32)
    return np.clip(x, 0.0, 1.0)[..., None], y
