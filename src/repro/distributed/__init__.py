from .sharding import NO_SHARD, Sharder, batch_partition_axes

__all__ = ["Sharder", "NO_SHARD", "batch_partition_axes"]
