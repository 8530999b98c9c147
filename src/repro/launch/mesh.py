"""Production mesh construction.

``make_production_mesh`` builds the target fleet meshes:

* single pod: 16×16 = 256 chips, axes ``(data, model)``
* multi-pod:  2×16×16 = 512 chips, axes ``(pod, data, model)`` — ``pod`` is
  the outer data-parallel axis (one cross-pod gradient all-reduce per step).

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets ``XLA_FLAGS`` for 512 host devices *before*
any jax import; tests and benches must keep seeing 1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0) -> Mesh:
    """Small mesh over however many (fake) devices the host exposes."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


def make_cluster_mesh(cores: int) -> Mesh:
    """1-D ``cores`` mesh for the SSR cluster layer (paper §5.3–5.5).

    One device per core, axis name ``cores`` — the mesh axis
    ``parallel/cluster.py`` shards streamed iteration spaces over.  Built
    from an explicit device list (never ``make_mesh``) so a host exposing
    more devices than cores still yields exactly the requested cluster.
    """
    import numpy as np

    devs = jax.devices()
    if cores < 1:
        raise ValueError(f"cores must be >= 1, got {cores}")
    if len(devs) < cores:
        raise ValueError(
            f"need {cores} devices for a {cores}-core cluster, have "
            f"{len(devs)}; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={cores} before "
            "importing jax")
    return Mesh(np.asarray(devs[:cores]), ("cores",))


def describe(mesh: Mesh) -> str:
    return "×".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names) + \
        f" ({mesh.size} chips)"
