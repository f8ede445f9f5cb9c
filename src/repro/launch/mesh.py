"""Production meshes (functions, not module constants — importing this file
never touches jax device state).

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the ``pod``
axis carries the cross-MES synchronisation in AFL training and extra batch
parallelism when serving.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small mesh for CI-scale sharding tests (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def force_host_device_count(n: int) -> None:
    """Simulate ``n`` host devices (CI meshes, parity suites, --mesh flags).

    Must run before the jax backend initialises (device count is fixed at
    first backend use); ``jax_num_cpu_devices`` raises after that.
    """
    jax.config.update("jax_num_cpu_devices", n)


def make_client_mesh(num_clients: int):
    """(data, model) mesh for the distributed AFL step on host devices.

    The ``data`` axis (which carries the stacked client axis of
    ``core.distributed``) takes the largest device count dividing
    ``num_clients``; ``model`` stays 1 — CPU parity runs shard clients,
    not parameters.  Returns None on a single device.
    """
    from jax.sharding import Mesh
    import numpy as np

    devs = jax.devices()
    use = max(k for k in range(1, len(devs) + 1) if num_clients % k == 0)
    if use <= 1:
        return None
    return Mesh(np.asarray(devs[:use]).reshape(use, 1), ("data", "model"))


def make_seed_mesh(num_seeds: int):
    """1-D mesh for the experiment engine's seed axis (repro/experiments).

    Uses the largest device count that divides ``num_seeds`` so the vmapped
    seed axis shards evenly; returns None on a single device (the vmap
    alone is the batching there).
    """
    from jax.sharding import Mesh
    import numpy as np

    devs = jax.devices()
    use = max(k for k in range(1, len(devs) + 1) if num_seeds % k == 0)
    if use <= 1:
        return None
    return Mesh(np.asarray(devs[:use]), ("seed",))
