"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e-256 pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is
pure data parallelism whose collectives cross the data-center network.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types on every axis."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


BATCH_AXIS = "batch"


def make_batch_mesh(num_devices: int | None = None):
    """1-D mesh over the batch axis for pod serving.

    The pod engine shards the *request* axis of a batched executable over
    this mesh, so the axis name is fixed (the engine's shard_map specs and
    the all-converged psum both reference it).  Defaults to every device
    jax can see; pass ``num_devices`` to run a pod on a subset.
    """
    n = len(jax.devices()) if num_devices is None else int(num_devices)
    if n < 1:
        raise ValueError("pod mesh needs at least one device")
    return make_mesh((n,), (BATCH_AXIS,))


def make_host_mesh(shape=None, axes=None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape, axes = (n,), ("data",)
    return make_mesh(shape, axes)


# TPU v5e hardware constants (per chip) for the roofline model.
HW = {
    "name": "tpu_v5e",
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_bw": 50e9,                # B/s per link (~4 links usable per chip)
    "dcn_bw": 6.25e9,              # B/s per host cross-pod (assumed 50 Gbit)
    "hbm_bytes": 16e9,
    "vmem_bytes": 128 * 2**20 / 8, # 16 MiB VMEM
}
