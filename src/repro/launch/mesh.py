"""Mesh construction: every mesh in the repo is built by :func:`make_mesh`.

Functions (NOT module-level constants) so importing this module never
touches jax device state.  Single-pod: (data=16, model=16) = 256 chips.
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the "pod" axis carries
only data-parallel gradient traffic (slow inter-pod links).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes on every dimension, over
    ``devices`` (default: this process's devices).

    JAX 0.9 makes ``Explicit`` axes by default, under which slicing a
    sharded array (a column's prefix on append) or an unannotated
    ``shard_map`` operand raises ``ShardingTypeError``; the engine relies
    on the compiler propagating shardings, which is what ``Auto`` means."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small meshes for tests (CPU host devices)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def make_shard_mesh(shards: int):
    """1-D ``("shards",)`` mesh over the first ``shards`` devices, for
    block-sharded table execution
    (:class:`repro.columnar.shard.ShardedTapeBackend`).

    Raises :class:`repro.columnar.config.ConfigError` when the process has
    fewer than ``shards`` devices — multi-device CPU runs must set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* the
    first jax import (see ``tests/test_shard.py`` for the subprocess
    pattern).
    """
    from ..columnar.config import ConfigError
    avail = jax.device_count()
    if shards > avail:
        raise ConfigError(
            f"shards={shards} but only {avail} jax device(s) visible; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "before the first jax import to simulate host devices")
    return make_mesh((shards,), ("shards",))
