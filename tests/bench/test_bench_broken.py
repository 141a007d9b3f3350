"""A whole run, past the look for a chip, decides ``correct`` from what the
timed path produced: sound, it passes; broken underneath, it fails."""
import argparse

import jax
import jax.numpy as jnp
import pytest

import run
from harness import compile_stats, device, train

from repro.core import server as fed_server


@pytest.fixture
def no_chip_check(monkeypatch, tmp_path):
    """Skip only the look for a TPU: the run goes on on the CPU, with its
    scratch files under the test's own directory."""
    monkeypatch.setattr(device, "require", lambda devices, chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(run, "ROOT", str(tmp_path))


def _run(cell, seconds=0.5):
    args = argparse.Namespace(workload=cell.name, seed=2**31 + 7,
                              seconds=seconds, trace=0)
    return run.execute(cell, args, jax.devices(),
                       compile_stats.CompileStats(), 0.0)


def test_sound_training_run_is_correct(tiny_cell, no_chip_check):
    cell = tiny_cell("paper_cnn_n10.train")
    cell.config["round_s"] = 0.1          # 0.5 s at 0.2 s a chunk: 3 chunks
    assert train.chunks(cell.config, 0.5) == 3
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"rounds_per_s", "setup_s"}
    assert out["attempted"] == 1 + 2 * 3 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_chunks_fill_the_seconds_asked_for():
    cfg = {"snapshot_every": 10, "round_s": 0.4}
    assert [train.chunks(cfg, s) for s in (0, 3, 4, 4.1, 10, 30)] == \
        [1, 1, 1, 2, 3, 8]


def test_unchanged_local_state_is_not_correct(tiny_cell, no_chip_check,
                                              monkeypatch):
    monkeypatch.setattr(fed_server, "client_update",
                        lambda loss_fn, params, data, key, cfg:
                        (params, jnp.float32(1.0)))
    out = _run(tiny_cell("paper_cnn_n10.train"))
    assert not out["correct"]
    assert out["checks"]["update0"]["value"] == pytest.approx(1.0, abs=1e-3)


def _carry_unchanged(step):
    def still(carry, ids):
        _, y = step(carry, ids)
        return carry, y
    return still


def _theta_unchanged(step):
    def still(carry, ids):
        new, y = step(carry, ids)
        return new._replace(gp=carry.gp), y
    return still


@pytest.mark.parametrize("fault", [_carry_unchanged, _theta_unchanged])
@pytest.mark.parametrize("workload", ["paper_cnn_n10.train",
                                      "xdevice_cnn_c256.train"])
def test_scanned_round_that_returns_its_state_is_not_correct(
        workload, fault, tiny_cell, no_chip_check, monkeypatch):
    """Only the scanned chunk is broken: round 0, the prologue, is sound."""
    real = fed_server.Federation._step_scan
    monkeypatch.setattr(fed_server.Federation, "_step_scan",
                        lambda self, data: fault(real(self, data)))
    out = _run(tiny_cell(workload))
    assert not out["correct"], out["checks"]
    for name in ("loss0", "update0", "wmean0"):
        if name in out["checks"]:
            c = out["checks"][name]
            assert c["value"] <= c["limit"], (name, c)


def test_half_batch_is_not_correct(tiny_cell, no_chip_check, monkeypatch):
    real = fed_server.client_update

    def half(loss_fn, params, data, key, cfg):
        def loss_half(p, b):
            return loss_fn(p, jax.tree.map(
                lambda a: a[: a.shape[0] // 2], b))
        return real(loss_half, params, data, key, cfg)

    monkeypatch.setattr(fed_server, "client_update", half)
    out = _run(tiny_cell("xdevice_cnn_c256.train"))
    assert not out["correct"], out["checks"]
