"""Production meshes (multi-pod dry-run spec) + per-arch derived views.

``make_production_mesh`` is the canonical deployment topology:
single pod = (16, 16) ("data", "model") = 256 chips (one TPU v5e pod);
multi-pod = (2, 16, 16) ("pod", "data", "model") = 512 chips.

Architectures do not all want the same (data, model) split — head counts,
expert counts and state widths impose divisibility — so sharding plans run
on a *derived view*: the same device array reshaped to
("pod", "data", "expert", "model") with data*expert*model = 256.  The
derived mesh is a pure relabeling; the physical topology (and therefore the
dry-run's collectives) is the production mesh's.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, Mesh

PER_POD = 256  # 16 x 16 chips


def make_merge_mesh(num_devices: Optional[int] = None) -> Optional[Mesh]:
    """1-D "kvs" mesh over the local devices for K-sharded storage-tier
    merge launches (``kernels.ops.lww_merge_many`` / ``vc_join_classify``
    under ``shard_map``: each device merges its local slab rows).

    The same mesh places device-resident arena slabs: with the device
    tier enabled, slab row capacities are rounded to a multiple of the
    mesh size and the (cap, D) value / (cap, 1) clock-node planes carry
    ``NamedSharding(mesh, P("kvs", None))``
    (``launch.sharding.kvs_slab_sharding``), so the donated in-place
    merge jits partition along K exactly like the shard_map launches.

    Returns None for a single device — the caller keeps the unsharded
    launch path unchanged.
    """
    n = jax.local_device_count() if num_devices is None else num_devices
    if n <= 1:
        return None
    return jax.make_mesh((n,), ("kvs",), axis_types=(AxisType.Auto,))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def derive_mesh(prod_mesh: Mesh, *, dp: int, ep: int, tp: int) -> Mesh:
    """Reshape the production mesh's devices to (pod, data, expert, model)."""
    assert dp * ep * tp == PER_POD, (dp, ep, tp)
    n_pods = prod_mesh.devices.size // PER_POD
    devices = prod_mesh.devices.reshape(n_pods, dp, ep, tp)
    return Mesh(devices, ("pod", "data", "expert", "model"),
                axis_types=(AxisType.Auto,) * 4)


def mesh_info(mesh: Mesh) -> str:
    return " x ".join(f"{k}={v}" for k, v in mesh.shape.items())
