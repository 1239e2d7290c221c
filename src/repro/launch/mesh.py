"""Production mesh construction.

A function, not a module-level constant: importing this module never touches
jax device state (device count is locked on first jax init; consumers that
need fake host devices call ``repro.launch.fake_devices`` first).
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules and
    ``with_sharding_constraint`` calls in this repo hand their axes to the
    partitioner, which ``Explicit`` axes (JAX's default since 0.7) forbid.
    Every mesh in the repo is built here."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16).
    Multi-pod: 2 pods x 256 chips as (pod=2, data=16, model=16); the pod axis
    is the DCN/outer-DP axis (hierarchical gradient reduction)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests)."""
    return make_mesh((data, model), ("data", "model"))


def make_conv_mesh(blocking):
    """Snap a conv processor grid onto a device mesh for ``shard_map``.

    ``blocking`` is a :class:`repro.core.parallel_tiling.ParallelBlocking`
    (or a plain axis->procs dict). The mesh always carries the four axes the
    distributed conv lowering shards over — ``("N", "cI", "hO", "wO")``, in
    that order, size 1 for unsplit axes — and uses the first ``P`` available
    devices (``P`` = the grid's processor count), so grids smaller than the
    host's device count work."""
    from repro.distributed.geometry import DIST_AXES, dist_grid

    sizes = dist_grid(blocking)
    P = math.prod(sizes)
    devs = jax.devices()
    if P > len(devs):
        raise ValueError(
            f"blocking grid {dict(zip(DIST_AXES, sizes))} needs {P} devices "
            f"but only {len(devs)} exist (launch.fake_devices(n) must run "
            f"before jax initializes)")
    return make_mesh(sizes, DIST_AXES, devices=devs[:P])
