from mmlspark_tpu.parallel.topology import (
    MeshSpec,
    build_mesh,
    distributed_init,
    local_device_count,
)
from mmlspark_tpu.parallel.sharding import (
    batch_sharding,
    bucket_ladder,
    bucket_target,
    replicated_sharding,
    named_sharding,
    pad_to_bucket,
    pad_to_multiple,
    padded_device_batch,
    round_to_multiple,
    shard_batch,
    unpad,
)
from mmlspark_tpu.parallel.dist import (
    placement_label,
    placement_report,
    put_batch,
    shard_state,
    state_shardings,
    state_specs,
    train_mesh,
)
from mmlspark_tpu.parallel.pipeline import (
    PipelineRunner,
    StagePlan,
    bubble_ratio,
    plan_stages,
    split_rows,
)
from mmlspark_tpu.parallel.ring_attention import (
    dense_attention,
    ring_attention,
    ring_attention_local,
)
from mmlspark_tpu.parallel.pallas_attention import (
    flash_attention,
    flash_attention_folded,
    flash_block_attn,
    folded_block_attn,
)

__all__ = [
    "MeshSpec",
    "dense_attention",
    "flash_attention",
    "flash_attention_folded",
    "flash_block_attn",
    "folded_block_attn",
    "ring_attention",
    "ring_attention_local",
    "build_mesh",
    "distributed_init",
    "local_device_count",
    "batch_sharding",
    "replicated_sharding",
    "named_sharding",
    "bucket_ladder",
    "bucket_target",
    "pad_to_bucket",
    "pad_to_multiple",
    "padded_device_batch",
    "round_to_multiple",
    "shard_batch",
    "unpad",
    "placement_label",
    "placement_report",
    "put_batch",
    "shard_state",
    "state_shardings",
    "state_specs",
    "train_mesh",
]
