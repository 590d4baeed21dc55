"""Elastic mesh selection for degraded-device serving.

A production pod loses hosts (8 chips each) without warning; serving must
keep running on whatever is left.  ``choose_mesh_shape`` picks the best
(data, model) factorization for an arbitrary device count — full healthy
pods get the canonical production shapes (launch/mesh.py), odd counts get
the largest model axis (<= the requested one) that still divides evenly.
``degraded_meshes`` enumerates the host-loss sequence so launchers can
pre-compile the fallback meshes before they are needed.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

HOST_SIZE = 8     # chips per host — the failure granularity
POD_SIZE = 256    # chips per pod (v5e-256)


def choose_mesh_shape(n_devices: int, *, model_axis: int = 16,
                      pod_size: int = POD_SIZE):
    """Device count -> (mesh shape, axis names).

    Multi-pod counts shard over ("pod", "data", "model") with the fixed
    production per-pod topology (16 x pod_size/16 — ``model_axis`` does
    not apply there); anything else gets ("data", "model") with the
    largest model axis <= ``model_axis`` that divides ``n_devices`` (a
    lost host rarely leaves a power of two).
    """
    if n_devices <= 0:
        raise ValueError(f"need at least one device, got {n_devices}")
    if (n_devices >= 2 * pod_size and n_devices % pod_size == 0
            and pod_size >= 16 and pod_size % 16 == 0):
        return ((n_devices // pod_size, 16, pod_size // 16),
                ("pod", "data", "model"))
    m = min(model_axis, n_devices)
    while n_devices % m:
        m -= 1
    return (n_devices // m, m), ("data", "model")


def degraded_meshes(n_devices: int, n_losses: int, *,
                    host_size: int = HOST_SIZE, model_axis: int = 16):
    """The host-loss degradation sequence: [(shape, names)] for the healthy
    mesh and each of ``n_losses`` successive lost hosts."""
    return [choose_mesh_shape(n_devices - i * host_size,
                              model_axis=model_axis)
            for i in range(n_losses + 1)]


def make_mesh(*, model_axis: int = 2, devices=None):
    """Build a Mesh over the devices that actually exist right now (the
    elastic analogue of launch/mesh.make_production_mesh)."""
    devices = jax.devices() if devices is None else list(devices)
    shape, names = choose_mesh_shape(len(devices), model_axis=model_axis)
    return jax.make_mesh(shape, names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(names))
