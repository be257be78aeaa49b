"""created_at (RateLimitReq field 10) — the caller-clock forward stamp.

A request's time base must travel WITH the request: the forward hop,
the degraded-mode reconcile queues, and the cross-region queues all
apply hits on another daemon LATER, and applying them at that daemon's
then-clock on a row living on the caller's base reads as expired —
bucket reset, debits silently gone (the concurrent cold-key
conservation loss).  These tests pin the codec plumbing end to end:
object ↔ TLV round trips, the C++ parser/packer, the bulk forward
stamp, and the packers' now-column override.
"""
import numpy as np
import pytest

from gubernator_tpu.core.batch import pack_columns, pack_requests
from gubernator_tpu.hashing import hash_request_keys
from gubernator_tpu.types import RateLimitRequest
from gubernator_tpu.wire import (req_from_tlv, req_to_tlv,
                                 tlv_created_at_payload, tlv_with_created)

DAY = 24 * 3_600_000
T0 = 1_700_000_000_000


def _req(key="k", created=0, hits=3):
    return RateLimitRequest(name="ca", unique_key=key, hits=hits,
                            limit=100, duration=DAY, created_at=created)


class TestWireCodec:
    def test_tlv_round_trip_carries_created_at(self):
        r = _req(created=T0 + 5)
        back = req_from_tlv(req_to_tlv(r))
        assert back.created_at == T0 + 5
        assert (back.name, back.unique_key, back.hits) == ("ca", "k", 3)

    def test_unset_created_at_stays_unset(self):
        back = req_from_tlv(req_to_tlv(_req(created=0)))
        assert back.created_at == 0

    def test_tlv_with_created_stamps_unstamped_slice(self):
        tlv = req_to_tlv(_req(created=0))
        stamped = tlv_with_created(tlv, T0 + 9)
        assert req_from_tlv(stamped).created_at == T0 + 9
        # other fields untouched
        assert req_from_tlv(stamped).hits == 3

    def test_payload_scan_last_value_wins(self):
        # proto3 scalar semantics: a second field-10 varint overrides
        tlv = tlv_with_created(req_to_tlv(_req(created=T0)), T0 + 77)
        assert req_from_tlv(tlv).created_at == T0 + 77

    def test_payload_scan_handles_all_wire_types(self):
        r = _req(created=T0 + 1)
        r.metadata["trace"] = "abc"  # length-delimited field 9
        payload = req_to_tlv(r)
        assert req_from_tlv(payload).created_at == T0 + 1
        assert tlv_created_at_payload(b"") == 0


class TestNativeCodec:
    @pytest.fixture(autouse=True)
    def _native(self):
        pytest.importorskip("gubernator_tpu.ops._native",
                            reason="needs the C++ codec")

    def test_parse_returns_created_column(self):
        from gubernator_tpu.ops import native

        data = req_to_tlv(_req("a", created=T0 + 3)) + \
            req_to_tlv(_req("b", created=0))
        parsed = native.parse_get_rate_limits(data)
        assert parsed is not None
        assert parsed["created_at"].tolist() == [T0 + 3, 0]

    def test_stamp_req_tlvs_stamps_only_unstamped(self):
        from gubernator_tpu.ops import native

        data = req_to_tlv(_req("a", created=T0 + 3)) + \
            req_to_tlv(_req("b", created=0))
        parsed = native.parse_get_rate_limits(data)
        out = native.stamp_req_tlvs(
            data, parsed["tlv_off"], parsed["tlv_len"],
            parsed["created_at"], T0 + 50)
        reparsed = native.parse_get_rate_limits(out)
        # first slice keeps the caller stamp (first hop wins), second
        # gets the forwarder's
        assert reparsed["created_at"].tolist() == [T0 + 3, T0 + 50]
        assert reparsed["hits"].tolist() == parsed["hits"].tolist()

    def test_pack_wire_wave_now_prefers_created(self):
        import numpy as np

        from gubernator_tpu.ops import native

        data = req_to_tlv(_req("a", created=T0 + 3)) + \
            req_to_tlv(_req("b", created=0))
        a64 = np.empty((8, 2), np.int64)
        res = native.pack_wire_wave(data, T0 + 99, a64,
                                    np.empty((3, 2), np.int32))
        assert res is not None
        n = res[0]
        assert n == 2
        assert a64[7].tolist() == [T0 + 3, T0 + 99]
        # the call's clocks, as the launch reads them: T0+3 then T0+99
        assert res[-1][3:] == (T0 + 3, T0 + 99, True)

    def test_pb2_fallback_paths_still_parse_stamped_tlvs(self):
        # pb2 treats field 10 as an unknown field: parses cleanly, and
        # the hand scan in req_from_tlv recovers the value
        from gubernator_tpu.proto import gubernator_pb2 as pb

        tlv = tlv_with_created(req_to_tlv(_req(created=0)), T0 + 4)
        msg = pb.GetRateLimitsReq.FromString(tlv)
        assert msg.requests[0].hits == 3


class TestPackers:
    def test_pack_requests_honors_created_at(self):
        reqs = [_req("a", created=T0 + 7), _req("b", created=0)]
        kh = hash_request_keys([r.name for r in reqs],
                               [r.unique_key for r in reqs])
        b, errs = pack_requests(reqs, T0 + 99, size=2, key_hashes=kh)
        assert not any(errs)
        assert b.now[:2].tolist() == [T0 + 7, T0 + 99]

    def test_pack_columns_honors_created_at(self):
        n = 3
        kh = np.arange(1, n + 1, dtype=np.uint64)
        z = np.zeros(n, np.int64)
        created = np.array([0, T0 + 5, 0], np.int64)
        b, errs = pack_columns(kh, z + 1, z + 10, z + DAY, z.copy(),
                               np.zeros(n, np.int32), z.copy(), T0 + 99,
                               created_at=created)
        assert not errs
        assert b.now.tolist() == [T0 + 99, T0 + 5, T0 + 99]

    def test_pack_columns_without_created_matches_legacy(self):
        n = 2
        kh = np.arange(1, n + 1, dtype=np.uint64)
        z = np.zeros(n, np.int64)
        b, _ = pack_columns(kh, z + 1, z + 10, z + DAY, z.copy(),
                            np.zeros(n, np.int32), z.copy(), T0)
        assert b.now.tolist() == [T0, T0]


class TestHitFlushKeepsTheStamp:
    """The deferred hit flush (GLOBAL reconcile, degraded-mode
    reconcile) rebuilds ONE aggregate request per key.  The aggregate
    must keep the stamp its queued requests were given: dropped, the
    owner applies the hits at its wall clock, and a row living on an
    older base reads as expired — the partition scenario's conservation
    loss once the wall clock had passed the lab's clock by a window."""

    def _flush(self, queue):
        from concurrent.futures import Future

        from gubernator_tpu.config import BehaviorConfig
        from gubernator_tpu.global_manager import GlobalManager
        from gubernator_tpu.metrics import Metrics

        sent = []

        class Peer:
            class info:
                grpc_address = "owner:1"

            def forward_raw(self, data, n):
                sent.append((data, n))
                f = Future()
                f.set_result(b"")
                return f

        class Inst:
            def owner_by_raw_khash(self, kh):
                return Peer()

            def is_self(self, peer):
                return False

            def default_hash_routing(self):
                return True

        gm = GlobalManager(Inst(), BehaviorConfig(
            global_sync_wait_ms=3_600_000), Metrics())
        try:
            queue(gm)
            gm._hits_tick()
        finally:
            gm.close()
        (data, n), = sent
        assert n == 1
        return req_from_tlv(data)

    def test_object_lane_aggregate_carries_the_latest_stamp(self):
        def queue(gm):
            gm.queue_hits(_req("a", created=T0 + 5, hits=2), degraded=True)
            gm.queue_hits(_req("a", created=T0 + 9, hits=3), degraded=True)

        back = self._flush(queue)
        assert (back.hits, back.created_at) == (5, T0 + 9)

    def test_raw_lane_aggregate_carries_its_tlvs_stamp(self):
        from gubernator_tpu.hashing import fnv1a64

        def queue(gm):
            kh = fnv1a64(b"ca_a")
            gm.queue_hits_raw(kh, req_to_tlv(_req("a", created=T0 + 5)), 2)
            gm.queue_hits_raw(kh, req_to_tlv(_req("a", created=T0 + 9)), 3)

        back = self._flush(queue)
        assert (back.hits, back.created_at) == (5, T0 + 9)
