"""Production mesh construction.

Single pod: 16 x 16 = 256 chips (v5e pod), axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis maps to the DCN/ICI-superpod boundary; batch and FSDP shard over
it, tensor-parallel stays within a pod.

Functions only — importing this module never touches jax device state.
Mesh construction goes through ``repro.compat.make_mesh`` (explicit ``Auto``
axis types).
"""
from __future__ import annotations

import warnings

import jax

from repro import compat


def make_data_mesh(n_devices: int) -> jax.sharding.Mesh:
    """1-D ``("data",)`` mesh over the first ``n_devices`` local devices —
    the mesh shape ``DynasparseEngine(mesh=...)`` shards row-stripe bands
    over.  Raises when the host doesn't have that many devices (e.g. a
    snapshot produced on an 8-device host replayed on a 1-device box)."""
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    avail = len(jax.devices())
    if n_devices > avail:
        raise ValueError(
            f"requested a {n_devices}-device data mesh but only {avail} "
            f"device(s) are visible (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N to force "
            f"host devices for testing)")
    return compat.make_mesh((n_devices,), ("data",))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Deprecated shim over :func:`make_mesh_for_devices` — the ONE
    validated mesh factory.  The fixed 16×16 (/ 2×16×16) shapes stay for
    callers that still use them, but the device count is now checked up
    front: previously ``multi_pod=True`` on a single host built a 512-chip
    mesh shape that only blew up (or silently mis-sharded) at first use."""
    warnings.warn(
        "make_production_mesh is deprecated; use "
        "make_mesh_for_devices(n_devices, model_parallel=..., pods=...)",
        DeprecationWarning, stacklevel=2)
    n = 512 if multi_pod else 256
    avail = len(jax.devices())
    if n > avail:
        raise ValueError(
            f"make_production_mesh(multi_pod={multi_pod}) needs {n} devices "
            f"but only {avail} device(s) are visible"
            + (" — a multi-pod mesh cannot be built on a single host"
               if multi_pod else ""))
    return make_mesh_for_devices(n, model_parallel=16,
                                 pods=2 if multi_pod else 1)


def make_mesh_for_devices(n_devices: int, *, model_parallel: int = 1,
                          pods: int = 1) -> jax.sharding.Mesh:
    """Elastic variant: largest (pod, data, model) mesh for a device count
    (used by distributed.elastic after failures)."""
    if n_devices < 1 or model_parallel < 1 or pods < 1:
        raise ValueError(
            f"mesh factors must be positive: n_devices={n_devices}, "
            f"model_parallel={model_parallel}, pods={pods}")
    if n_devices % (model_parallel * pods) != 0:
        raise ValueError(
            f"n_devices={n_devices} is not divisible by "
            f"model_parallel*pods={model_parallel * pods} "
            f"(model_parallel={model_parallel}, pods={pods}); "
            f"cannot form a rectangular (pod, data, model) mesh")
    data = n_devices // (model_parallel * pods)
    if pods > 1:
        return compat.make_mesh((pods, data, model_parallel),
                                ("pod", "data", "model"))
    return compat.make_mesh((data, model_parallel), ("data", "model"))
