"""Device-mesh helpers (the TPU analog of the reference's
``platform/nccl_helper.h`` NCCLContextMap: which devices participate and
how they are wired).

On TPU the wiring is the ICI torus; ``jax.sharding.Mesh`` names its axes
and XLA routes collectives over it.  Axis convention used throughout:

* ``dp``  — data parallel (batch sharding, gradient psum)
* ``fsdp`` — fully-sharded data parallel (batch sharding AND ZeRO-style
  parameter/optimizer-state sharding: XLA reduce-scatters grads and
  all-gathers params around each use — see spec_layout.py)
* ``tp``  — tensor/model parallel (weight-column sharding)
* ``pp``  — pipeline stages (scan-over-stages layer sharding)
* ``sp``  — sequence/context parallel (ring attention)
* ``ep``  — expert parallel (MoE / sharded embeddings)
"""

import numpy as np

import jax
from jax.sharding import Mesh


def shard_map_norep(f, mesh, in_specs, out_specs):
    """shard_map with replication (varying-manual-axes) checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

__all__ = ["make_mesh", "shard_map_norep", "AXIS_DP", "AXIS_FSDP",
           "AXIS_TP", "AXIS_PP", "AXIS_SP", "AXIS_EP"]

AXIS_DP = "dp"
AXIS_FSDP = "fsdp"
AXIS_TP = "tp"
AXIS_PP = "pp"
AXIS_SP = "sp"
AXIS_EP = "ep"


def make_mesh(shape=None, axis_names=None, devices=None):
    """Build a Mesh.

    ``make_mesh()``                  -> 1-D dp mesh over all devices
    ``make_mesh(8)``                 -> dp mesh over 8 devices
    ``make_mesh((4, 2))``            -> (dp, tp) mesh
    ``make_mesh((2, 2, 2), ("dp", "tp", "sp"))``
    ``make_mesh((1, 2, 2), ("dp", "fsdp", "tp"))``  -> the model-parallel
    mesh spec_layout.py's sharding rules target (dp and fsdp both shard
    the batch; fsdp additionally ZeRO-shards params/optimizer state; tp
    column-shards attention/ffn weights)
    """
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices),)
    elif isinstance(shape, int):
        shape = (shape,)
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(
            "mesh shape %r needs %d devices, have %d"
            % (shape, n, len(devices))
        )
    if axis_names is None:
        axis_names = (AXIS_DP, AXIS_TP, AXIS_PP, AXIS_SP, AXIS_EP)[:len(shape)]
    if len(axis_names) != len(shape):
        raise ValueError("axis_names length must match mesh shape rank")
    arr = np.asarray(devices[:n]).reshape(shape)
    return Mesh(arr, tuple(axis_names))
