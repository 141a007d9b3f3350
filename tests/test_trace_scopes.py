"""The round program's device scopes and the engine driver's host spans.

Every phase of a round runs under a ``jax.named_scope`` (``fl.local_phase``,
``fl.w_build``, ``fl.coalition_round``, ``fl.eval``), in the round-0
prologue and in each engine's scanned step alike, so the name stack that
reaches a profiler trace says which phase an op belongs to.  The driver
wraps its host work in ``jax.profiler.TraceAnnotation`` spans (``fl.run``
and the eight below it), on the profiler's clock.  Neither changes what
the federation computes.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, sim
from repro.core.client import ClientConfig
from repro.core.server import Federation, FederationConfig
from repro.data import synthetic
from repro.models import cnn
from repro.serve import ModelStore

SCOPES = {"fl.local_phase", "fl.w_build", "fl.coalition_round", "fl.eval"}
SPANS = ("fl.cohort_schedule", "fl.prologue", "fl.dispatch", "fl.publish",
         "fl.checkpoint", "fl.emit", "fl.history")
N_CLIENTS = 4


@pytest.fixture(scope="module")
def tiny():
    """A CNN cut to 4/8/16 channels and units, 4 clients of 20 digits."""
    x, y = synthetic.digits(N_CLIENTS * 20 + 20, seed=0)
    x, y = jnp.asarray(x), jnp.asarray(y)
    data = {"x": x[:80].reshape(N_CLIENTS, 20, 28, 28, 1),
            "y": y[:80].reshape(N_CLIENTS, 20)}
    xe, ye = x[80:], y[80:]
    params = cnn.init(jax.random.key(0), cnn.CNNConfig(c1=4, c2=8, fc=16))
    return data, params, lambda p: cnn.accuracy(p, xe, ye)


def _fed(tiny, engine: str, rounds: int = 3) -> Federation:
    _, _, eval_fn = tiny
    cfg = FederationConfig(
        n_clients=N_CLIENTS, n_coalitions=2, rounds=rounds,
        method="coalition",
        client=ClientConfig(epochs=1, batch_size=10, lr=0.05),
        engine=engine, sim=sim.SimConfig())
    return Federation(cnn.loss_fn, eval_fn, cfg)


def _scopes(hlo: str) -> set[str]:
    names = re.findall(r'op_name="([^"]*)"', hlo)
    return {c for n in names for c in n.split("/") if c.startswith("fl.")}


@pytest.mark.parametrize("engine", ["scan", "semi_async", "event_driven"])
def test_every_phase_is_scoped_in_prologue_and_chunk(tiny, engine):
    data, params, _ = tiny
    fed = _fed(tiny, engine)
    key = jax.random.key(1)
    round0 = fed._round0_jit.lower(params, data, key, None).compile()
    assert _scopes(round0.as_text()) == SCOPES
    prologue = getattr(fed, f"_prologue_{fed._spec_of(engine)}")
    carry, _ = jax.eval_shape(lambda: prologue(params, data, key))
    chunk = fed._chunk_program(engine, 2).lower(carry, data).compile()
    assert _scopes(chunk.as_text()) == SCOPES


def _run(fed, tiny, work, tag: str):
    data, params, _ = tiny
    sink = obs.InMemorySink()
    gp, hist = fed.run(params, data, jax.random.key(2), snapshot_every=1,
                       store=ModelStore(str(work / f"store_{tag}")),
                       ckpt_every=1, ckpt_dir=str(work / f"ckpt_{tag}"),
                       sink=sink)
    return gp, hist, sink.records


def _host_spans(trace_dir) -> list[tuple[int, int, str]]:
    from jax.profiler import ProfileData

    paths = sorted(trace_dir.rglob("*.xplane.pb"))
    assert paths, f"the profiler wrote no trace under {trace_dir}"
    pdata = ProfileData.from_file(str(paths[-1]))
    return [(ev.start_ns, ev.end_ns, ev.name)
            for plane in pdata.planes if not plane.name.startswith("/device")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("fl.")]


def test_driver_spans_nest_in_run_and_leave_results_bit_identical(
        tiny, tmp_path):
    fed = _fed(tiny, "scan")
    gp0, h0, rec0 = _run(fed, tiny, tmp_path, "plain")
    with jax.profiler.trace(str(tmp_path / "trace")):
        gp1, h1, rec1 = _run(fed, tiny, tmp_path, "traced")
    spans = _host_spans(tmp_path / "trace")
    runs = [(s, e) for s, e, n in spans if n == "fl.run"]
    assert len(runs) == 1
    lo, hi = runs[0]
    names = [n for _, _, n in spans]
    for name in SPANS:
        assert name in names, name
    assert all(lo <= s and e <= hi for s, e, _ in spans)
    # round 0 and two chunks of one round: a publish and a checkpoint at
    # each of the three boundaries, a call of each chunk program
    assert names.count("fl.dispatch") == 2
    assert names.count("fl.publish") == names.count("fl.checkpoint") == 3

    for a, b in zip(jax.tree.leaves(gp0), jax.tree.leaves(gp1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for f0, f1 in zip(h0.trace, h1.trace):
        if f0 is not None:
            np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
    assert len(rec0) == len(rec1)
