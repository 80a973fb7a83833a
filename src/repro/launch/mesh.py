"""Production mesh builders.

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches jax device state; the dry-run entry point
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import, smoke tests see the real single CPU device.

Topology: one v5e pod = 16x16 = 256 chips -> ("data", "model") axes; the
multi-pod mesh adds a leading "pod"=2 axis (512 chips) over DCN. The GNN
runtime flattens every axis into one partition axis (paper: N GPUs = N
partitions); the LM runtime uses FSDP over ("pod","data") and TP/EP over
"model"; DLRM row-shards tables over the flattened mesh.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (device count forced by caller)."""
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    # Auto axes: shardings are propagated by the compiler (jax.make_mesh
    # defaults to Explicit axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def flat_axes(mesh) -> tuple[str, ...]:
    from ..dist import api as dist_api
    return dist_api.flat_axes(mesh)


def n_devices(mesh) -> int:
    from ..dist import api as dist_api
    return dist_api.mesh_size(mesh)


# TPU v5e hardware constants for the roofline terms (per chip).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link (we model one active link/chip)
