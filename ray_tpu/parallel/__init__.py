from ray_tpu.dag.stage_pipeline import StagePipeline
from ray_tpu.parallel.mesh import (MeshConfig, build_mesh, get_slice_info,
                                   fake_mesh)
from ray_tpu.parallel.sharding import (ShardingRules, ShardingStrategy,
                                       shard_params, batch_sharding,
                                       strategy_from_name)

__all__ = [
    "MeshConfig", "build_mesh", "get_slice_info", "fake_mesh",
    "ShardingRules", "ShardingStrategy", "shard_params", "batch_sharding",
    "strategy_from_name", "StagePipeline",
]
