"""TPU parallelism: meshes, collectives, sharded train steps, ring attention.

This package is the ICI data plane of the framework (SURVEY.md §2.4's
"TPU-native equivalent"): DP/FSDP/TP/SP all expressed as jax sharding over a
Mesh, with the elastic RPC stack (broker/group/accumulator) as the DCN
control plane around it.
"""

from .mesh import (  # noqa: F401
    AXES,
    check_disjoint,
    initialize_distributed,
    local_batch_size,
    make_mesh,
    named,
    parse_mesh_spec,
    replicated,
    shard_batch_spec,
    split_mesh,
)
from .collectives import (  # noqa: F401
    all_gather_axis,
    redistribute,
    reduce_scatter_axis,
    ring_permute,
    tree_pmean,
    tree_psum,
)
from .ring_attention import full_attention, ring_attention, ring_attention_sharded  # noqa: F401
from .train import (  # noqa: F401
    auto_shardings,
    fsdp_spec,
    make_train_step,
    mirror_shardings,
    param_shardings,
)
from .moe import SwitchMoE, moe_param_spec, moe_shardings  # noqa: F401
from .pipeline import pipeline_apply  # noqa: F401
