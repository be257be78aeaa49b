"""Multi-chip parallelism: key-space sharding over a device mesh and
GLOBAL-behavior replication via ICI collectives (SURVEY.md §2.3).

Replaces the reference's peer fan-out (hash.go peer picking +
peer_client.go gRPC forwarding + global.go broadcast goroutines) with
sharded tables under shard_map and psum delta reconciliation — inside a
pod there are no "peers", just mesh axes.
"""
from .mesh import make_mesh, shard_table, table_sharding  # noqa: F401
from .sharded import ShardedEngine, make_sharded_step  # noqa: F401
