"""Device-mesh parallelism: the framework's single distribution abstraction.

Reference parity: SURVEY.md §2 "DP / comms backend" rows and §5.8. The
reference had two sibling backends (TPU CrossShardOptimizer over ICI;
fork-side NCCL MirroredStrategy). The rebuild has exactly one: a
`jax.sharding.Mesh` plus NamedSharding annotations — XLA inserts the
collectives (psum over ICI within a slice, DCN across slices).
"""

from tensor2robot_tpu.parallel.mesh import (
    create_mesh,
    batch_sharding,
    replicated_sharding,
    shard_batch,
    local_batch_slice,
)
from tensor2robot_tpu.parallel.ring_attention import (
    dense_attention_reference,
    ring_attention,
)
from tensor2robot_tpu.parallel.ulysses_attention import (
    ulysses_attention,
)
from tensor2robot_tpu.parallel.pipeline import (
    pipeline_apply,
    stack_stage_params,
)
from tensor2robot_tpu.parallel.expert_parallel import (
    MoEParams,
    expert_parallel_moe,
    init_moe_params,
    moe_share,
    route,
)
from tensor2robot_tpu.parallel.tp_rules import (
    infer_dense_tp_specs,
    infer_dense_tp_specs_from_model,
    infer_fsdp_specs,
    infer_fsdp_specs_from_model,
    specs_to_shardings,
)

__all__ = [
    "create_mesh",
    "batch_sharding",
    "replicated_sharding",
    "shard_batch",
    "local_batch_slice",
    "ring_attention",
    "ulysses_attention",
    "dense_attention_reference",
    "pipeline_apply",
    "stack_stage_params",
    "MoEParams",
    "expert_parallel_moe",
    "init_moe_params",
    "moe_share",
    "route",
    "infer_dense_tp_specs",
    "infer_dense_tp_specs_from_model",
    "infer_fsdp_specs",
    "infer_fsdp_specs_from_model",
    "specs_to_shardings",
]
