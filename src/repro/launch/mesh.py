"""Mesh construction: the transformer dry-run's production meshes
(DESIGN.md §5) and the packed-BNN serving mesh (DESIGN.md §10).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (device count is locked at first jax init, and
smoke tests must see 1 CPU device while the dry-run sees 512 placeholder
hosts).
"""

from __future__ import annotations

import math

import jax
import numpy as np

# Both meshes carry the full (pod, data, model) axis-name set so one
# sharding-rule table serves both; single-pod just has pod=1.
SINGLE_POD = (1, 16, 16)              # 256 chips
MULTI_POD = (2, 16, 16)               # 512 chips


def make_serving_mesh(n_devices: int | None = None) -> jax.sharding.Mesh:
    """1-D data-parallel mesh for the packed-BNN serving stack.

    Unlike :func:`make_production_mesh` there is no 256-chip assumption:
    the serving mesh is ``("data",)`` over the first ``n_devices``
    devices (default: all of them), because the packed model is tiny
    (~1.75 MB — XNOR-Net's 32x memory saving) and is REPLICATED on every
    device; only the batch shards. The forward is then collective-free:
    each device runs the whole network on its batch slice (DESIGN.md
    §10).

    On an accelerator the mesh takes the host's chips; asking for more
    than it has is an error that names them. Simulated scale-out on the
    CPU uses forced host devices exactly like the dry-run path: set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE the
    first jax backend touch (``tests/conftest.py`` and
    ``benchmarks/scaling.py`` both do).
    """
    devices = jax.devices()
    n = len(devices) if n_devices is None else int(n_devices)
    if n < 1:
        raise ValueError(f"serving mesh needs >= 1 device, got {n}")
    if len(devices) < n:
        platform = devices[0].platform
        if platform != "cpu":
            raise RuntimeError(
                f"need {n} devices for the serving mesh, but this host has "
                f"{len(devices)} {platform} device(s) "
                f"({devices[0].device_kind})"
            )
        raise RuntimeError(
            f"need {n} devices for the serving mesh, have {len(devices)}"
            " — simulated scale-out must set XLA_FLAGS=--xla_force_"
            f"host_platform_device_count={n} before any jax device use"
        )
    return jax.sharding.Mesh(np.asarray(devices[:n]), ("data",))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) == n:
        # real fleet: ICI-adjacency-aware assignment
        return jax.make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before any jax import"
        )
    # dry-run: 512 placeholder hosts, single-pod uses the first 256
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(dev_array, axes)
