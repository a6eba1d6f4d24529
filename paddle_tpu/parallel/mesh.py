"""Mesh construction + collective context.

Reference mapping (SURVEY.md §5.8): ``ring_id``-keyed NCCL communicators
(collective_helper.h:62 NCCLCommContext) become named mesh axes;
``gen_nccl_id`` + ``c_comm_init`` bootstrap becomes
``jax.distributed.initialize`` + Mesh construction; hierarchical inter/exter
rings (nccl_helper.h:252-307) become a 2-level ICI×DCN mesh.
"""

from __future__ import annotations

import os


def _jax():
    import jax

    return jax


def shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check (the
    lowerings' collectives are written against explicit axis names)."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def build_mesh(axes, devices=None):
    """Build a Mesh with named axes, e.g. {"dcn": n_slices, "data": 8}.

    Axis order puts DCN-scale axes first so the fastest-varying (last) axis
    maps to ICI neighbors — collectives on "data"/"model" ride ICI, only the
    leading axis crosses DCN (the hierarchical-allreduce layout)."""
    import numpy as np

    jax = _jax()
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    names = list(axes.keys())
    sizes = [int(axes[n]) for n in names]
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError(
            "mesh needs %d devices, only %d available" % (total, len(devices))
        )
    dev_array = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(dev_array, names)


def build_data_mesh(num_devices=None, devices=None):
    jax = _jax()
    if devices is None:
        devices = jax.devices()
    n = num_devices or len(devices)
    return build_mesh({"data": n}, devices)


def initialize_distributed(
    coordinator_address=None, num_processes=None, process_id=None
):
    """Multi-host bootstrap (reference: c_gen_nccl_id_op.cc:37-108 runs a
    temp gRPC server to broadcast ncclUniqueId; here jax.distributed runs the
    equivalent handshake over DCN)."""
    jax = _jax()
    coordinator_address = coordinator_address or os.environ.get(
        "PADDLE_COORDINATOR", os.environ.get("JAX_COORDINATOR_ADDRESS")
    )
    num_processes = num_processes or int(
        os.environ.get("PADDLE_TRAINERS_NUM", os.environ.get("JAX_NUM_PROCESSES", 1))
    )
    process_id = (
        process_id
        if process_id is not None
        else int(os.environ.get("PADDLE_TRAINER_ID", os.environ.get("JAX_PROCESS_ID", 0)))
    )
    if num_processes > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )


class CommContext(object):
    """ring_id -> mesh-axis registry (reference: NCCLCommContext keyed by
    ring_id, platform/collective_helper.h:62)."""

    _instance = None

    def __init__(self):
        self._meshes = {}  # ring_id -> (mesh, axis_name)

    @classmethod
    def instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def register(self, ring_id, mesh, axis_name="data"):
        self._meshes[int(ring_id)] = (mesh, axis_name)

    def get(self, ring_id=0):
        return self._meshes.get(int(ring_id))

    def has(self, ring_id=0):
        return int(ring_id) in self._meshes


def pad_to_multiple(flat, n):
    """Zero-pad a 1-D array to a multiple of n (collective tiling
    helper shared by optimizer_sharding / quantized_allreduce).
    -> (padded, original_size)."""
    import jax.numpy as jnp

    size = flat.shape[0]
    pad = (-size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, size
