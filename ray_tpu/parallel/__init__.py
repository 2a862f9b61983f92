"""TPU-native parallelism layer.

This package is the framework's answer to everything NCCL/DDP-shaped in the
reference (Torch-DDP backend `train/torch/config.py:102-113`, collective lib
`python/ray/util/collective/`): a device-mesh abstraction with named axes for
every parallelism strategy (dp / fsdp / tp / pp / sp / ep), a logical-axis
sharding-rule engine that maps parameter pytrees onto the mesh, and a
multi-host mesh coordinator that rides the runtime's placement groups the way
`jax.distributed` rides its coordination service.

Collectives are XLA programs over ICI (psum / all_gather / ppermute /
reduce_scatter inside jit), never a sidecar library.
"""

from .mesh import (  # noqa: F401
    MeshSpec,
    MESH_AXES,
    create_mesh,
    auto_mesh_shape,
    local_mesh,
    mesh_shape_for,
)

_SHARDING_NAMES = (
    "ShardingRules", "logical_to_mesh_axes", "named_sharding",
    "pytree_shardings", "shard_pytree", "constrain", "batch_sharding",
    "DP_RULES", "FSDP_RULES", "TP_RULES", "FSDP_TP_RULES",
)


def __getattr__(name):
    # the sharding rules need jax; drivers import this package for
    # MeshSpec alone and stay off it
    if name in _SHARDING_NAMES:
        from . import sharding
        return getattr(sharding, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
