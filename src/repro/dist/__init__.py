"""``repro.dist`` — SPMD sharding subsystem (DESIGN.md §3).

Mesh-role derivation and PartitionSpec rules (:mod:`repro.dist.sharding`),
and layout-agnostic collectives for shard_map bodies
(:mod:`repro.dist.collectives`).
"""
from repro.dist.collectives import (  # noqa: F401
    all_to_all_scatter, axis_size, gather_slices, gather_workers,
    psum_axes, worker_slice_index,
)
from repro.dist.sharding import (  # noqa: F401
    MODEL_AXIS_NAMES, cache_pspec, model_axes_of, param_pspec_fsdp,
    tree_pspecs, worker_axes_of,
)
