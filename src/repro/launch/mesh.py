"""Production mesh builders (spec-mandated shapes).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes, devices):
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def _require_devices(ndev: int, shape) -> list:
    """The first ``ndev`` jax devices, or the actionable XLA_FLAGS error
    every mesh builder raises (a short device list would otherwise build
    a silently wrong-shaped mesh)."""
    devices = jax.devices()
    if len(devices) < ndev:
        raise RuntimeError(
            f"need {ndev} devices for mesh {shape}; got {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{max(ndev, 8)} BEFORE importing jax (launch/dryrun.py "
            "does this)")
    return devices[:ndev]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    ndev = int(np.prod(shape))
    return _make_mesh(shape, axes, _require_devices(ndev, shape))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires forced host device count)."""
    import numpy as np
    ndev = int(np.prod(shape))
    return _make_mesh(shape, axes, _require_devices(ndev, shape))


def make_ep_mesh(ep: int, *, replica: int = 0):
    """The (1, ep) serving mesh of DP replica ``replica`` (DESIGN.md
    §16): axes ("data", "model") with the experts sharded over "model"
    (mixed_moe's EP axis) and a size-1 data axis — data parallelism is
    N whole engine REPLICAS (serving/ep.DPReplicaGroup), not an in-mesh
    axis, so each replica's mesh owns the disjoint device slice
    ``[replica*ep, (replica+1)*ep)``. Raises the actionable XLA_FLAGS
    error when the host does not expose enough devices."""
    ep = int(ep)
    if ep < 1:
        raise ValueError(f"ep must be >= 1, got {ep}")
    if replica < 0:
        raise ValueError(f"replica must be >= 0, got {replica}")
    ndev = (replica + 1) * ep
    devices = _require_devices(ndev, (1, ep))[replica * ep:]
    return _make_mesh((1, ep), ("data", "model"), devices)
