from .rules import (  # noqa: F401
    ShardingCtx,
    abstract_sharded,
    axis_size,
    current_ctx,
    current_mesh,
    logical_sharding,
    set_ctx,
    shard,
    shard_map,
    use_ctx,
)
