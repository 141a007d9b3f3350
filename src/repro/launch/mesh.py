"""Device-mesh construction (TPU v5e pods; host-device placeholders in the
dry-run; forced-host-platform CPU meshes for the sharded federation).  All
FUNCTIONS, not module-level constants — importing this module never touches
jax device state.

``parse_mesh`` is the CLI entry (``train.py --mesh data=8``): a spec string
names either a canonical mesh (``host`` | ``production``) or explicit axis
sizes (``data=8`` / ``data=4,model=2``).  On CPU, multi-device meshes need
``XLA_FLAGS=--xla_force_host_platform_device_count=K`` set *before* jax
initialises — the error messages say so rather than assuming a pod.
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) single-pod / (2, 16, 16) two-pod mesh.

    Axes: ``data`` carries batch / FL clients / D-sharded federation tiles
    (and FSDP-style expert sharding), ``model`` carries tensor parallelism,
    ``pod`` carries the cross-pod data-parallel replica.

    Raises ValueError when fewer devices exist than the pod shape wants: a
    silent fall-back to a smaller mesh would run a "production" job on
    whatever one host holds.  Use ``host`` or an explicit ``data=N`` spec for
    a smaller mesh.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes)
    if len(devices) < n:
        raise ValueError(
            f"production mesh {shape} needs {n} devices, have "
            f"{len(devices)}; use --mesh host or data=N for a smaller mesh "
            "(on CPU set XLA_FLAGS=--xla_force_host_platform_device_count, "
            "or run under dryrun.py for the full shape)")
    # more devices than needed (e.g. 512 placeholders, single-pod 256 mesh)
    arr = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(arr, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the real local devices (CPU smoke tests / examples)."""
    n = len(jax.devices())
    data = n // model
    arr = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return jax.sharding.Mesh(arr, ("data", "model"))


def parse_mesh(spec: str):
    """Mesh from a CLI spec: ``host`` | ``production`` | ``axis=N[,axis=M]``.

    Explicit specs build over the first ``prod(sizes)`` local devices with the
    axes in the order given (``data=8`` ⇒ an 8-way data mesh; ``data=4,model=2``
    ⇒ (4, 2)).  Validation is eager — an unsatisfiable spec raises ValueError
    at :class:`~repro.core.server.Federation` construction, not mid-run.
    """
    spec = spec.strip()
    if spec == "host":
        return make_host_mesh()
    if spec == "production":
        return make_production_mesh()
    sizes: dict[str, int] = {}
    for part in spec.split(","):
        if "=" not in part:
            raise ValueError(
                f"bad mesh spec {spec!r}: expected 'host', 'production', or "
                "comma-separated axis=N pairs like 'data=8'")
        name, _, val = part.partition("=")
        name = name.strip()
        try:
            size = int(val)
        except ValueError:
            raise ValueError(
                f"bad mesh spec {spec!r}: axis size {val!r} is not an int"
            ) from None
        if size < 1:
            raise ValueError(f"bad mesh spec {spec!r}: {name} must be >= 1")
        if name in sizes:
            raise ValueError(f"bad mesh spec {spec!r}: duplicate axis {name!r}")
        sizes[name] = size
    if "data" not in sizes:
        raise ValueError(f"bad mesh spec {spec!r}: a 'data' axis is required")
    n = int(np.prod(list(sizes.values())))
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(
            f"mesh {spec!r} needs {n} devices, have {len(devices)}; on CPU "
            "set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before starting python")
    arr = np.asarray(devices[:n]).reshape(tuple(sizes.values()))
    return jax.sharding.Mesh(arr, tuple(sizes))


def mesh_spec(mesh) -> str:
    """The canonical ``axis=N,...`` string of a mesh (for run metadata)."""
    return ",".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names)


def batch_axes(mesh) -> tuple:
    """The mesh axes that jointly shard the global batch."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
