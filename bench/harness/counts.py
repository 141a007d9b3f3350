"""Operations and bytes the algorithm needs, counted from shapes.

These are the numerators of the utilisation and roofline metrics.  They
count what the work requires, not what an implementation happens to move:
padding, copies and recomputation are left out on purpose, so that a
program which wastes them reads a lower share.
"""
from __future__ import annotations

#: device-op name prefixes of the fused coalition round's two Pallas
#: kernels; a round calls each once
COALITION_KERNELS = ("center_sq_dists", "fused_coalition_stats")


def cnn_forward_flops(cfg: dict) -> int:
    """FLOPs of one image's forward pass through the paper's CNN.

    Two valid 5x5 convolutions each followed by a 2x2/2 max-pool, then two
    dense layers; 2 FLOPs per multiply-add.  Bias adds, ReLU and pooling
    are left out (under 1% of the total).  8,534,016 at the paper's widths.
    """
    k, c1, c2, fc, n_cls, hw = (cfg["kernel"], cfg["c1"], cfg["c2"],
                                cfg["fc"], cfg["n_classes"], cfg["in_hw"])
    s1 = hw - k + 1                       # conv1 output side
    p1 = s1 // 2
    s2 = p1 - k + 1                       # conv2 output side
    p2 = s2 // 2
    macs = (s1 * s1 * c1 * k * k * 1
            + s2 * s2 * c2 * k * k * c1
            + p2 * p2 * c2 * fc
            + fc * n_cls)
    return 2 * macs


def cnn_params(cfg: dict) -> int:
    """Parameter count D of the CNN (582,026 at the paper's widths)."""
    k, c1, c2, fc, n_cls, hw = (cfg["kernel"], cfg["c1"], cfg["c2"],
                                cfg["fc"], cfg["n_classes"], cfg["in_hw"])
    p2 = ((hw - k + 1) // 2 - k + 1) // 2
    return (k * k * c1 + c1 + k * k * c1 * c2 + c2
            + p2 * p2 * c2 * fc + fc + fc * n_cls + n_cls)


def round_flops(cfg: dict) -> int:
    """Model FLOPs of one federation round.

    Every trained image costs a forward and a backward pass (3x forward);
    every test image of the in-round eval one forward.  Matmuls and
    convolutions in f32 at default precision run as bf16 passes on the TPU,
    so this count is set against the chip's bf16 peak.
    """
    fwd = cnn_forward_flops(cfg["model"])
    trained = cfg["n_clients"] * cfg["examples_per_client"] * cfg["local_epochs"]
    return 3 * fwd * trained + fwd * cfg["n_test"]


def w_pass_bytes(n: int, d: int, itemsize: int = 4) -> int:
    """Bytes of the two streaming reads of the unpadded (N, D) matrix W."""
    return 2 * n * d * itemsize


def coalition_round_bytes(n: int, d: int, k: int, itemsize: int = 4) -> int:
    """HBM bytes one coalition round needs: two reads of W, and one write
    each of the K barycenter rows and the θ row.  The (N, K) distance and
    (K, N) membership blocks are under a kilobyte and left out."""
    return w_pass_bytes(n, d, itemsize) + (k + 1) * d * itemsize
