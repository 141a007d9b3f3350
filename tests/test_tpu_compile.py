"""Compile the FL kernels and rounds for a described TPU v5e — no chip.

The TPU compiler is installed with jax; it compiles for a v5e topology that
is described, not attached, and refuses what the chip would refuse (tiling,
VMEM limits, unpartitionable kernels) — which Pallas interpret mode on the
CPU never checks.  The topology is described inside a module fixture, never
at import: only one process may load the TPU library, and every pytest
worker imports every test file.  Keep these tests in this one file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import fused as fz
from repro.core import sharded
from repro.kernels import fused_round as fr
from repro.kernels import ops
from repro.kernels import pairwise_dist as pd
from repro.kernels import segment_mean as sm

N, K = 10, 3
#: the paper's CNN (582,026 parameters) and an 8M-parameter model
D_SIZES = (582_026, 8_388_608)
NATIVE = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 topology, with the persistent compilation cache
    off: what is compiled for a described chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                t = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — no TPU compiler here
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def native_kernels(monkeypatch):
    """Steer ``repro.kernels.ops`` to native kernels: on this CPU host it
    would pick interpret mode from ``jax.default_backend()``."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(name, d, sh):
    w = _sds((N, d), sh)
    kn = _sds((K, N), sh)
    return {"pairwise_sq_dists": (pd.pairwise_sq_dists, (w,)),
            "sq_dists_to_points": (pd.sq_dists_to_points,
                                   (w, _sds((K, d), sh))),
            "segment_sum": (sm.segment_sum, (kn, w)),
            "center_sq_dists": (fr.center_sq_dists, (w, kn)),
            "fused_coalition_stats": (fr.fused_coalition_stats, (w, kn)),
            }[name]


@pytest.mark.parametrize("d", D_SIZES)
@pytest.mark.parametrize("name", ["pairwise_sq_dists", "sq_dists_to_points",
                                  "segment_sum", "center_sq_dists",
                                  "fused_coalition_stats"])
def test_fl_kernel_compiles_for_v5e(one_chip, name, d):
    fn, args = _kernel_args(name, d, one_chip)
    compiled = fn.lower(*args, block_d=16384, interpret=False).compile()
    assert NATIVE in compiled.as_text()


def test_fused_round_pallas_compiles_for_v5e(one_chip, native_kernels):
    w = _sds((N, D_SIZES[0]), one_chip)
    ci = _sds((K,), one_chip, jnp.int32)
    text = jax.jit(lambda w_, c_: fz.fused_round_pallas(w_, c_)
                   ).lower(w, ci).compile().as_text()
    # one kernel per pass
    assert sum(NATIVE in line for line in text.splitlines()) >= 2


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_sharded_round_compiles_on_described_mesh(topo, native_kernels,
                                                  backend):
    """The mesh-parallel round on a described 4-chip data mesh: W comes in
    whole (as the engine builds it), is zero-padded and D-sharded into the
    shard_map, and the passes are stitched by all-reduces."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    sb = sharded.sharded_backend(backend, mesh)
    w = _sds((N, D_SIZES[0]), NamedSharding(mesh, P()))
    ci = _sds((K,), NamedSharding(mesh, P()), jnp.int32)
    text = jax.jit(lambda w_, c_: fz.fused_round(w_, c_, backend=sb)
                   ).lower(w, ci).compile().as_text()
    assert "all-reduce" in text
    assert (NATIVE in text) == (backend == "pallas")
