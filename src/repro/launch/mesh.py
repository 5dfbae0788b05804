"""Production mesh construction.

Built as a FUNCTION (not a module-level constant) so importing this module
never touches jax device state -- required because the dry-run must set
``xla_force_host_platform_device_count`` *before* first jax init.
"""
from __future__ import annotations

import numpy as np

import jax


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto (compiler-chosen)
    sharding mode."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    assert n % model == 0
    return _make_mesh((n // model, model), ("data", "model"))


def make_data_mesh(n_shards: int = None, axis: str = "data"):
    """1-D mesh over ``n_shards`` devices (default: all) on one named
    axis -- the default mesh of the sharded relational ``parallel``
    engine (repro.core.parallel, DESIGN.md section 9)."""
    n_avail = len(jax.devices())
    if n_shards is None:
        n_shards = n_avail
    if n_shards > n_avail:
        raise ValueError(f"requested {n_shards} shards but only "
                         f"{n_avail} devices exist")
    # Mesh directly (not jax.make_mesh): a subset of the host devices is
    # a legal data mesh, e.g. 2 shards on a 4-device host.
    devs = np.asarray(jax.devices()[:n_shards])
    return jax.sharding.Mesh(devs, (axis,))
