"""The FLOP and byte counters against the hand counts."""
import json
import os

import pytest

from harness import counts

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_cnn_forward_flops_hand_count():
    # conv1 24*24*32*25 + conv2 8*8*64*800 + fc1 1024*512 + fc2 512*10 MACs
    macs = 460_800 + 3_276_800 + 524_288 + 5_120
    assert counts.cnn_forward_flops(_config("paper_cnn_n10")["model"]) \
        == 2 * macs == 8_534_016


@pytest.mark.parametrize("name", ["paper_cnn_n10", "xdevice_cnn_c256"])
def test_cnn_params_match_the_configuration(name):
    cfg = _config(name)
    assert counts.cnn_params(cfg["model"]) == cfg["model"]["params"] \
        == 582_026


@pytest.mark.parametrize("name,tflop", [("paper_cnn_n10", 1.621),
                                        ("xdevice_cnn_c256", 0.216)])
def test_round_flops(name, tflop):
    # 3 x 8.534 MFLOP per trained image plus 8.534 MFLOP per eval image
    assert counts.round_flops(_config(name)) / 1e12 == pytest.approx(
        tflop, abs=5e-4)


@pytest.mark.parametrize("n,mb", [(10, 46.56), (256, 1191.99)])
def test_w_pass_bytes(n, mb):
    assert counts.w_pass_bytes(n, 582_026) / 1e6 == pytest.approx(mb,
                                                                  abs=0.01)


def test_coalition_round_bytes_adds_the_output_rows():
    d = 582_026
    assert counts.coalition_round_bytes(10, d, 3) \
        == counts.w_pass_bytes(10, d) + 4 * d * 4
