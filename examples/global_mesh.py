"""Example: GLOBAL rate limits on the mesh-resident replica tier.

The reference implements Behavior=GLOBAL with a hit queue + owner
broadcasts over gRPC (global.go).  On a pod, ``global_mode="mesh"``
replaces that whole subsystem with a replicated table: every chip
holds a replica of the GLOBAL rows, a request is decided on its key's
HOME replica (so every answer is exact), and ONE collective fold per
sync tick brings the other replicas up to it — traffic per tick is
O(tier size), independent of request rate.

Run: python examples/global_mesh.py
(set JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4
 to simulate a 4-chip pod on CPU)
"""
import time

from gubernator_tpu.config import BehaviorConfig, Config
from gubernator_tpu.core.table import to_host
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.types import Behavior, RateLimitRequest


def main() -> None:
    inst = V1Instance(Config(
        cache_size=1 << 16,
        global_mode="mesh",          # pod-local GLOBAL → the mesh tier
        behaviors=BehaviorConfig(global_sync_wait_ms=100)))
    now = int(time.time() * 1000)

    def wave(n, t):
        reqs = [RateLimitRequest(name="login", unique_key="tenant-42",
                                 hits=1, limit=100_000, duration=60_000,
                                 behavior=Behavior.GLOBAL)
                for _ in range(n)]
        return inst.get_rate_limits(reqs, now_ms=t)

    wave(32, now)  # the first touch pins the key
    mge = inst._meshglobal
    print(f"mesh keys pinned: {len(mge.slots) if mge else 0}")

    t0 = time.perf_counter()
    rs = []
    for w in range(4):  # MAX_BATCH_SIZE is 1000, like the reference
        rs.extend(wave(1000, now + 1 + w))
    dt = time.perf_counter() - t0
    print(f"4000 GLOBAL decisions in {dt * 1e3:.1f}ms (home replica, "
          f"no queues); remaining after them: {rs[-1].remaining}")
    assert rs[-1].remaining == 100_000 - 32 - 4000

    # the sync tick (every global_sync_wait_ms) is ONE collective fold —
    # the entire reconcile step; wait for it to have taken every hit
    slot = mge.slots[next(iter(mge.slots))]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        st = mge.stats()
        rem = set(to_host(mge.state)["remaining"][:, slot].tolist())
        if st["folded_hits"] == st["injected_hits"] and len(rem) == 1:
            break
        time.sleep(0.05)
    print(f"after the fold: {st['folded_hits']} of {st['injected_hits']} "
          f"hits folded, every replica's remaining: {sorted(rem)}")
    assert st["folded_hits"] == st["injected_hits"] == 4032
    assert rem == {100_000 - 4032}
    inst.close()


if __name__ == "__main__":
    main()
