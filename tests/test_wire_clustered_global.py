"""Clustered GLOBAL through the columnar wire lane (VERDICT r2 item 3).

Round 2's lane demoted any clustered batch containing a GLOBAL row to
the pb2 object path — the hottest production shape (GLOBAL keys on a
multi-peer ring) was the one that lost the C++ lane.  These tests pin
the fix: GLOBAL rows ride `wire_clustered` (answered from the local
replica, per global.go semantics — SURVEY §3.3), their async reconcile
is queued as raw TLV prototypes (no per-request objects on the request
path), and the owner/replica convergence matches the object path's.
"""
import time

import pytest

from gubernator_tpu import Algorithm, Behavior, Oracle, RateLimitRequest
from gubernator_tpu import cluster as cluster_mod
from gubernator_tpu.config import BehaviorConfig
from gubernator_tpu.proto import gubernator_pb2 as pb

DAY = 24 * 3_600_000


def clock_ms() -> int:
    return int(time.time() * 1000)


def serialize(reqs):
    msg = pb.GetRateLimitsReq()
    for r in reqs:
        m = msg.requests.add()
        m.name = r.name
        m.unique_key = r.unique_key
        m.hits = r.hits
        m.limit = r.limit
        m.duration = r.duration
        m.algorithm = int(r.algorithm)
        m.behavior = int(r.behavior)
        m.burst = r.burst
    return msg.SerializeToString()


def lane_count(inst, lane: str) -> float:
    return inst.metrics.wire_lane_counter.labels(lane=lane)._value.get()


def check_wire(inst, reqs, now=None):
    out = pb.GetRateLimitsResp.FromString(
        inst.get_rate_limits_wire(serialize(reqs),
                                  now_ms=now if now is not None
                                  else clock_ms()))
    return list(out.responses)


def g_req(key, hits=1, limit=100, name="wcg"):
    return RateLimitRequest(name=name, unique_key=key, hits=hits,
                            limit=limit, duration=DAY,
                            behavior=Behavior.GLOBAL)


class TestClusteredGlobalWireLane:
    @pytest.fixture(scope="class")
    def cluster(self):
        c = cluster_mod.start(3, behaviors=BehaviorConfig(
            global_sync_wait_ms=40, global_broadcast_interval_ms=40,
            global_timeout_ms=5000),
            cache_size=1 << 12)
        yield c
        c.stop()

    def _non_owner(self, cluster, key: str):
        """A daemon that does NOT own ``key`` (full name_key form)."""
        owner_d = cluster.owner_daemon_of(key)
        for i in range(3):
            if cluster.daemon_at(i) is not owner_d:
                return cluster.instance_at(i), i
        raise AssertionError("unreachable")

    def test_global_rides_columnar_lane_with_local_replica_semantics(
            self, cluster):
        """A pure-GLOBAL batch through a non-owner: wire_clustered lane,
        zero pb2 fallback, decisions = fresh local replica (oracle)."""
        inst, _ = self._non_owner(cluster, "wcg_a0")
        reqs = [g_req(f"a{i % 4}", hits=1 + i % 2) for i in range(16)]
        before = lane_count(inst, "wire_clustered")
        fallback_before = lane_count(inst, "pb2_fallback")
        now = clock_ms()
        want = Oracle().check_batch(reqs, now)
        got = check_wire(inst, reqs, now)
        assert len(got) == len(reqs)
        for i, (g, e) in enumerate(zip(got, want)):
            assert g.error == "", (i, g.error)
            assert (int(g.status), int(g.remaining), int(g.limit)) == \
                (int(e.status), int(e.remaining), int(e.limit)), i
        assert lane_count(inst, "wire_clustered") - before == len(reqs)
        assert lane_count(inst, "pb2_fallback") == fallback_before

    def test_hits_reconcile_to_owner_and_broadcast_back(self, cluster):
        """global.go semantics over the wire lane: hits served on a
        non-owner's replica converge to the owner within the sync
        window, then every replica converges via the broadcast."""
        name, key = "wcg2", "conv"
        inst, _ = self._non_owner(cluster, f"{name}_{key}")
        [r] = check_wire(inst, [g_req(key, hits=5, name=name)])
        assert r.error == "" and int(r.remaining) == 95

        def remaining_at(i):
            [rr] = check_wire(cluster.instance_at(i),
                              [g_req(key, hits=0, name=name)])
            return int(rr.remaining)

        owner_d = cluster.owner_daemon_of(f"{name}_{key}")
        owner_i = next(i for i in range(3)
                       if cluster.daemon_at(i) is owner_d)
        deadline = time.time() + 10
        while time.time() < deadline and remaining_at(owner_i) != 95:
            time.sleep(0.05)
        assert remaining_at(owner_i) == 95, \
            "owner never applied wire-queued async hits"
        deadline = time.time() + 10
        while time.time() < deadline and any(
                remaining_at(i) != 95 for i in range(3)):
            time.sleep(0.05)
        assert [remaining_at(i) for i in range(3)] == [95] * 3, \
            "replicas did not converge via broadcast"

    def test_owner_entry_queues_broadcast(self, cluster):
        """A GLOBAL batch through the OWNER daemon's wire lane must
        broadcast merged state to the replicas (queue_update_raw)."""
        name, key = "wcg3", "ownr"
        owner_d = cluster.owner_daemon_of(f"{name}_{key}")
        owner_i = next(i for i in range(3)
                       if cluster.daemon_at(i) is owner_d)
        inst = cluster.instance_at(owner_i)
        before = lane_count(inst, "wire_clustered")
        [r] = check_wire(inst, [g_req(key, hits=7, name=name)])
        assert r.error == "" and int(r.remaining) == 93
        assert lane_count(inst, "wire_clustered") - before == 1

        def remaining_at(i):
            [rr] = check_wire(cluster.instance_at(i),
                              [g_req(key, hits=0, name=name)])
            return int(rr.remaining)

        deadline = time.time() + 10
        while time.time() < deadline and any(
                remaining_at(i) != 93 for i in range(3)):
            time.sleep(0.05)
        assert [remaining_at(i) for i in range(3)] == [93] * 3, \
            "owner-side wire batch never broadcast to replicas"

    def test_mixed_batch_splits_global_local_rest_forwarded(self, cluster):
        """GLOBAL rows answer locally while sibling non-GLOBAL rows in
        the same batch still ring-forward, all in one columnar pass."""
        inst, _ = self._non_owner(cluster, "wcg4_m0")
        reqs = []
        for i in range(10):
            reqs.append(g_req(f"m{i}", name="wcg4"))
            reqs.append(RateLimitRequest(
                name="wcg4", unique_key=f"p{i}", hits=1, limit=9,
                duration=DAY, algorithm=Algorithm.TOKEN_BUCKET))
        before = lane_count(inst, "wire_clustered")
        now = clock_ms()
        want = Oracle().check_batch(reqs, now)
        got = check_wire(inst, reqs, now)
        for i, (g, e) in enumerate(zip(got, want)):
            assert g.error == "", (i, g.error)
            assert (int(g.status), int(g.remaining)) == \
                (int(e.status), int(e.remaining)), (i, reqs[i])
        assert lane_count(inst, "wire_clustered") - before == len(reqs)

    def test_global_sharing_owner_with_forward_not_double_debited(
            self, cluster):
        """A GLOBAL row whose owner also receives forwarded non-GLOBAL
        rows from the same batch must NOT ride the forward sub-batch:
        it is answered locally and reconciles async — forwarding it too
        would debit the owner twice (and overwrite the local answer)."""
        name = "wcg6"
        inst, serving_i = self._non_owner(cluster, f"{name}_seed")
        # find a GLOBAL key and a plain key with the SAME remote owner
        gkey = pkey = None
        for i in range(300):
            k = f"x{i}"
            d = cluster.owner_daemon_of(f"{name}_{k}")
            if d is cluster.daemon_at(serving_i):
                continue
            if gkey is None:
                gkey, gowner = k, d
            elif pkey is None and d is gowner:
                pkey = k
                break
        assert gkey and pkey
        reqs = [g_req(gkey, hits=6, name=name),
                RateLimitRequest(name=name, unique_key=pkey, hits=1,
                                 limit=9, duration=DAY)]
        got = check_wire(inst, reqs)
        # GLOBAL answered from the (fresh) local replica
        assert got[0].error == "" and int(got[0].remaining) == 94
        assert got[1].error == "" and int(got[1].remaining) == 8
        owner_i = next(i for i in range(3)
                       if cluster.daemon_at(i) is gowner)

        def owner_remaining():
            [rr] = check_wire(cluster.instance_at(owner_i),
                              [g_req(gkey, hits=0, name=name)])
            return int(rr.remaining)

        # after reconcile the owner must have applied the hits exactly
        # once: 94, never 88 (double debit via forward + async queue)
        deadline = time.time() + 10
        while time.time() < deadline and owner_remaining() == 100:
            time.sleep(0.05)
        assert owner_remaining() == 94, \
            f"owner saw {100 - owner_remaining()} hits, expected 6"
        # and it must STAY 94 across further flush ticks
        time.sleep(0.3)
        assert owner_remaining() == 94

    def test_wire_and_object_path_share_one_reconcile_stream(self, cluster):
        """The same key served through the wire lane AND the object path
        between flushes must reconcile the SUM of both lanes' hits to
        the owner (the raw queue merges into the object queue)."""
        name, key = "wcg5", "both"
        inst, _ = self._non_owner(cluster, f"{name}_{key}")
        [r1] = check_wire(inst, [g_req(key, hits=3, name=name)])
        resp2 = inst.get_rate_limits([g_req(key, hits=4, name=name)],
                                     now_ms=clock_ms())[0]
        assert r1.error == "" and resp2.error == ""

        owner_d = cluster.owner_daemon_of(f"{name}_{key}")
        owner_i = next(i for i in range(3)
                       if cluster.daemon_at(i) is owner_d)

        def owner_remaining():
            [rr] = check_wire(cluster.instance_at(owner_i),
                              [g_req(key, hits=0, name=name)])
            return int(rr.remaining)

        deadline = time.time() + 10
        while time.time() < deadline and owner_remaining() != 93:
            time.sleep(0.05)
        assert owner_remaining() == 93, \
            "owner saw only one lane's hits (expected 3+4 reconciled)"
