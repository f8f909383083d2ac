"""ray_tpu.parallel — mesh construction, sharding rules, multi-host bootstrap.

All parallelism strategies (DP/FSDP/TP/PP/SP/EP) are expressed as
mesh-axis shardings of one jitted program (SURVEY.md §2.3, §7).
"""

from ray_tpu.parallel.mesh import (
    AXIS_ORDER,
    DCN_AXES,
    MeshSpec,
    build_mesh,
    flat_axes,
    mesh_axis_size,
    single_device_mesh,
)
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    constrain,
    named_sharding,
    shard_batch,
    spec_for,
    tree_shardings,
)
from ray_tpu.parallel.bootstrap import (
    HostGroupSpec,
    configure_compilation_cache,
    initialize_host,
    local_process_specs,
    megascale_env,
    shutdown_host,
    watch_compiles,
)

__all__ = [
    "AXIS_ORDER",
    "DCN_AXES",
    "MeshSpec",
    "build_mesh",
    "single_device_mesh",
    "mesh_axis_size",
    "flat_axes",
    "DEFAULT_RULES",
    "spec_for",
    "named_sharding",
    "tree_shardings",
    "constrain",
    "shard_batch",
    "HostGroupSpec",
    "configure_compilation_cache",
    "initialize_host",
    "megascale_env",
    "shutdown_host",
    "local_process_specs",
    "watch_compiles",
]
