"""Thin wrappers over the jax API surface the SPMD code and the calibration
subsystem use, so call sites name one place.

- ``make_mesh`` builds meshes with explicit ``Auto`` axis types;
- ``shard_map`` is ``jax.shard_map`` with its ``check_vma`` validation
  spelled ``check``;
- ``backend_kind`` / ``device_kind`` are the device probes the calibration
  subsystem keys its measurements on and the fallback hardware model is
  chosen by (``repro.core.perfmodel.runtime_fallback``).
"""
from __future__ import annotations

import jax


def backend_kind() -> str:
    """The active jax backend ("cpu", "tpu", "gpu")."""
    return jax.default_backend()


def device_kind() -> str:
    """``device_kind`` of the first device ("TPU v5 lite" on TPU v5e,
    "cpu" on the host backend): measurements taken on one chip generation
    must never be replayed on another."""
    return jax.devices()[0].device_kind


def make_mesh(axis_shapes, axis_names) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map``; ``check`` toggles its ``check_vma`` replication-
    mismatch validation."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
