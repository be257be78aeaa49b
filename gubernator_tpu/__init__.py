"""gubernator_tpu — a TPU-native distributed rate-limiting framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of
gardod/gubernator (see SURVEY.md): token/leaky-bucket rate limiting over
millions of keys, batched GetRateLimits API, hash-sharded key ownership
across a TPU mesh, GLOBAL replication via ICI collectives, pluggable
persistence and peer discovery.

Counter state lives as an HBM-resident struct-of-arrays; each request
batch executes as one jit-compiled gather→update→scatter program; a pod
acts as a single coherent rate-limit region via psum delta sync instead of
gRPC peer fan-out.
"""

__version__ = "0.1.0"


from .types import (  # noqa: F401
    Algorithm,
    Behavior,
    GetRateLimitsRequest,
    GetRateLimitsResponse,
    GregorianDuration,
    HealthCheckResponse,
    MAX_BATCH_SIZE,
    PeerInfo,
    RateLimitRequest,
    RateLimitResponse,
    Status,
)
from .oracle import Oracle  # noqa: F401

# Service layer (lazy-import-safe: these pull in grpc/jax on use).
from .config import (  # noqa: F401
    BehaviorConfig,
    Config,
    DaemonConfig,
    setup_daemon_config,
)
from .store import CacheItem, FileLoader, MockLoader, MockStore  # noqa: F401


def __getattr__(name):
    """Lazy heavyweight exports: V1Instance, Daemon, spawn_daemon, Client."""
    if name in ("V1Instance",):
        from .instance import V1Instance

        return V1Instance
    if name in ("Daemon", "spawn_daemon"):
        from . import daemon

        return getattr(daemon, name)
    if name in ("Client", "HttpClient"):
        from . import client

        return getattr(client, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
