"""round_mfu: the whole federation round's share of the chip's peak, in
percent.

Model FLOPs per round (``harness/counts.round_flops``: a forward and a
backward pass, 3x the forward, for every trained image, and one forward for
every test image of the in-round eval) times the rounds completed in the
traced window, over the window's seconds and the chip's bf16 peak.  The
CNN's f32 matmuls and convolutions at default precision run as bf16 passes
on the TPU, so the bf16 peak is the one they are held to.  Moves
``rounds_per_s``.
"""
from harness import counts


def read(ctx):
    if ctx["kind"] != "train":
        return None
    flops = counts.round_flops(ctx["config"]) * ctx["rounds"]
    return 100.0 * flops / ctx["window_s"] / ctx["peaks"]["bf16_flops"]
