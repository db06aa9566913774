"""Mesh construction. IMPORTANT: functions, never module-level constants —
importing this module must not touch jax device state (the dry-run forces a
512-device host platform and must do so before any jax initialization).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

from repro.config import MeshConfig


def gspmd(mesh):
    """``mesh`` with every axis ``Auto``. The model code leaves layouts to
    GSPMD propagation, while ``jax.make_mesh`` types axes ``Explicit`` by
    default, under which a worker ``vmap`` over differently-sharded leaves
    and a gather from a vocab-sharded table are refused. Every sharding
    the train and serve steps build goes through here."""
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment: one v5e pod = (data=16, model=16) = 256 chips;
    two pods add a leading 'pod' axis = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return gspmd(jax.make_mesh(shape, axes))


def make_mesh(cfg: MeshConfig):
    return gspmd(jax.make_mesh(cfg.shape, cfg.axis_names))


def make_worker_mesh(p: int, *, simulate_host_devices: bool = False):
    """One CentralVR worker per device, for the convex spmd backend
    (``core/spmd.py``, DESIGN.md §2).  ``simulate_host_devices=True``
    forces the CPU host platform to present p devices through the shared
    ``spmd.force_host_devices`` helper — call it before the first jax
    operation (the helper errors once the backend is initialized)."""
    from jax._src import xla_bridge

    from repro.core import spmd

    if simulate_host_devices:
        spmd.force_host_devices(p)
        # force_host_devices validates against the GLOBAL device count,
        # which in a jax.distributed world can satisfy p while THIS
        # process holds fewer — worker_mesh would then build a mesh over
        # devices it cannot address and fail much later with an opaque
        # shard_map shape error.  Catch the mismatch here, with the
        # remediation options spelled out (DESIGN.md §2).
        if (xla_bridge.backends_are_initialized()
                and jax.local_device_count() < p):
            raise RuntimeError(
                f"make_worker_mesh(p={p}, simulate_host_devices=True): jax "
                f"is already initialized and this process has only "
                f"{jax.local_device_count()} local device(s) "
                f"(global count: {jax.device_count()}).  Simulated host "
                "devices must be configured before the first jax "
                "operation.  Either start a fresh process, call "
                "spmd.force_host_devices(p) before any jax op, export "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={p}, "
                "or use backend='vmap' (DESIGN.md §2)")
    return spmd.worker_mesh(p)


def make_test_mesh(devices: Optional[int] = None,
                   model_axis: int = 2):
    """Small mesh over whatever devices exist (tests force 8 host devices
    via a subprocess; plain test runs see (1, 1))."""
    n = devices or len(jax.devices())
    model = model_axis if n % model_axis == 0 and n > 1 else 1
    return gspmd(jax.make_mesh((n // model, model), ("data", "model")))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def worker_axes(mesh, vr_workers: str) -> Tuple[str, ...]:
    """Which mesh axes carry CentralVR worker copies.

    'data' — paper-faithful: one worker per data-axis group (params
             replicated along these axes), includes 'pod' when present.
    'pod'  — hierarchical (optimized): workers across pods, FSDP inside.
    'none' — plain data-parallel (no VR worker copies).
    """
    names = mesh.axis_names
    if vr_workers == "none":
        return ()
    if vr_workers == "pod":
        return ("pod",) if "pod" in names else ()
    if vr_workers == "data":
        return tuple(a for a in ("pod", "data") if a in names)
    raise ValueError(vr_workers)


def worker_count(mesh, vr_workers: str) -> int:
    sizes = mesh_axis_sizes(mesh)
    n = 1
    for a in worker_axes(mesh, vr_workers):
        n *= sizes[a]
    return max(n, 1)
