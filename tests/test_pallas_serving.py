"""GUBER_ENGINE=pallas — the fused serving engine (ISSUE 8).

One device program per wave: decisions + on-device heavy-hitter tap
(+ the mesh-GLOBAL replica decide and accumulator scatter when that
tier is bound).  Pins:

- engine selection (auto on TPU, compiled XLA flavor on CPU opt-in,
  legacy GUBER_STEP_IMPL untouched, loud fallback on construction
  failure — no error rows);
- byte-parity vs the ShardedEngine oracle on seeded wire + object
  traffic, single- and multi-shard;
- 16-caller exact conservation through the fused dispatcher path;
- mesh-GLOBAL fused-scatter conservation (folded == injected) under
  global_psum / device_step faults;
- the PhaseLedger collapse: fused waves carry no `pack` segment and
  the exact wave-time partition (sum of segments == duration) holds —
  the proof of what fusion deleted;
- the device tap feeds the heavy-hitter sketch without host copies.
"""
import threading

import numpy as np
import pytest

from gubernator_tpu.config import BehaviorConfig, Config
from gubernator_tpu.hashing import hash_key, hash_request_keys
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.parallel.pallas_engine import (
    PallasServingEngine, XlaFusedEngine, resolve_engine_kind)
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.types import Behavior, RateLimitRequest

NOW = 1_790_000_000_000


def ser(reqs):
    m = pb.GetRateLimitsReq()
    for r in reqs:
        q = m.requests.add()
        q.name, q.unique_key = r.name, r.unique_key
        q.hits, q.limit, q.duration = r.hits, r.limit, r.duration
        q.behavior = int(r.behavior)
        q.algorithm = int(r.algorithm)
    return m.SerializeToString()


def req(key, name="fs", **kw):
    d = dict(hits=1, limit=1_000_000, duration=600_000)
    d.update(kw)
    return RateLimitRequest(name=name, unique_key=key, **d)


def seeded_reqs(seed, n=120, keys=17, **kw):
    rng = np.random.default_rng(seed)
    return [req(f"k{int(k) % keys}", **kw)
            for k in rng.zipf(1.2, size=n)]


@pytest.fixture()
def clean_env(monkeypatch):
    monkeypatch.delenv("GUBER_ENGINE", raising=False)
    monkeypatch.delenv("GUBER_STEP_IMPL", raising=False)
    return monkeypatch


def fused_instance(n=1, **cfg):
    d = dict(cache_size=1 << 12, sweep_interval_ms=0, engine="pallas")
    d.update(cfg)
    return V1Instance(Config(**d), mesh=make_mesh(n=n))


class TestEngineSelection:
    def test_resolver_matrix(self):
        r = resolve_engine_kind
        # auto: fused pallas on TPU, classic elsewhere (pre-ISSUE-8
        # default preserved on CPU)
        assert r("", "xla", "cpu") == "xla-classic"
        assert r("auto", "xla", "cpu") == "xla-classic"
        assert r("", "xla", "tpu") == "pallas-fused"
        # explicit opt-in: fused everywhere, compiled XLA flavor off-TPU
        assert r("pallas", "xla", "cpu") == "xla-fused"
        assert r("pallas", "xla", "tpu") == "pallas-fused"
        assert r("xla", "pallas", "cpu") == "xla-classic"
        assert r("sharded", "xla", "tpu") == "xla-classic"
        # legacy knob keeps meaning the bucket-kernel engine
        assert r("", "pallas", "cpu") == "pallas-kernel"
        # GUBER_ENGINE wins when both are set
        assert r("pallas", "pallas", "cpu") == "xla-fused"
        with pytest.raises(ValueError, match="GUBER_ENGINE"):
            r("bogus", "xla", "cpu")

    def test_cpu_opt_in_builds_compiled_fused_engine(self, clean_env):
        inst = fused_instance()
        try:
            assert isinstance(inst.engine, XlaFusedEngine)
            assert inst.engine.fused_serving and inst.engine.fused_tap
            # analytics sink wired before serving
            assert inst.engine.tap_sink is not None
        finally:
            inst.close()

    def test_env_overrides_config(self, clean_env):
        clean_env.setenv("GUBER_ENGINE", "xla")
        inst = fused_instance()  # Config says pallas; env wins
        try:
            assert type(inst.engine) is ShardedEngine
        finally:
            inst.close()

    def test_selected_engine_that_cannot_build_stops_the_daemon(
            self, clean_env, monkeypatch):
        """No stand-in engine: a selected engine whose construction
        fails propagates out of V1Instance (and so out of
        spawn_daemon) instead of serving from ShardedEngine."""
        import gubernator_tpu.parallel.pallas_engine as pe

        def boom(self, *a, **kw):
            raise RuntimeError("no fused engine on this stack")

        monkeypatch.setattr(pe.XlaFusedEngine, "__init__", boom)
        with pytest.raises(RuntimeError, match="no fused engine"):
            fused_instance()


class TestFusedParity:
    def test_wire_and_object_byte_parity_vs_sharded(self, clean_env):
        """The acceptance pin: identical seeded traffic through the
        fused engine and the classic XLA path — responses byte-equal
        on the wire lane, field-equal on the object lane."""
        fi = fused_instance()
        xi = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0,
                               engine="xla"), mesh=make_mesh(n=1))
        try:
            datas = [ser(seeded_reqs(s, limit=40)) for s in range(4)]
            outs_f = [fi.get_rate_limits_wire(d, now_ms=NOW + i)
                      for i, d in enumerate(datas)]
            outs_x = [xi.get_rate_limits_wire(d, now_ms=NOW + i)
                      for i, d in enumerate(datas)]
            assert outs_f == outs_x  # byte identity, deny region incl.
            of = fi.get_rate_limits(seeded_reqs(9, limit=40),
                                    now_ms=NOW + 10)
            ox = xi.get_rate_limits(seeded_reqs(9, limit=40),
                                    now_ms=NOW + 10)
            assert [(int(a.status), a.remaining, a.reset_time, a.limit,
                     a.error) for a in of] == \
                   [(int(b.status), b.remaining, b.reset_time, b.limit,
                     b.error) for b in ox]
        finally:
            fi.close()
            xi.close()

    def test_multishard_engine_parity(self):
        """Direct engine A/B on a 2-shard mesh (the dryrun shape)."""
        fe = XlaFusedEngine(make_mesh(n=2), capacity_per_shard=1 << 9)
        xe = ShardedEngine(make_mesh(n=2), capacity_per_shard=1 << 9,
                           batch_per_shard=64)
        reqs = seeded_reqs(3, n=96, keys=23, limit=25)
        for t in (0, 1, 2, 30):
            rf = fe.check_batch(reqs, NOW + t)
            rx = xe.check_batch(reqs, NOW + t)
            for i, (a, b) in enumerate(zip(rf, rx)):
                assert (int(a.status), a.remaining, a.reset_time,
                        a.limit) == (int(b.status), b.remaining,
                                     b.reset_time, b.limit), (t, i)
        assert fe.over_count == xe.over_count
        assert fe.insert_count == xe.insert_count

    def test_pallas_kernel_flavor_emits_device_tap(self):
        """The Mosaic-kernel flavor (interpret off-TPU) emits the same
        fused tap: khash/hits/over rows match the wave's decisions."""
        from gubernator_tpu.core.batch import pack_requests

        taps = []
        pe = PallasServingEngine(make_mesh(n=1),
                                 capacity_per_shard=1 << 9,
                                 batch_per_shard=64)
        pe.tap_sink = taps.append
        reqs = [req(f"t{i % 3}", limit=2) for i in range(8)]
        kh = hash_request_keys([r.name for r in reqs],
                               [r.unique_key for r in reqs])
        batch, _ = pack_requests(reqs, NOW, size=len(reqs),
                                 key_hashes=kh)
        st, _, _, _, full = pe.check_packed(batch, kh, NOW)
        assert not full.any()
        tap = np.asarray(taps[-1])
        served = tap[3] != 0
        assert int(served.sum()) == len(reqs)
        assert set(tap[0][served].view(np.uint64).tolist()) == \
            set(np.asarray(kh).tolist())
        # over flags in the tap == over decisions in the outputs
        assert int(tap[2][served].sum()) == int((np.asarray(st) == 1)
                                                .sum())


class TestFusedConservation:
    def test_16_caller_exact_conservation(self, clean_env):
        """16 threads hammer shared keys through the fused dispatcher
        path; every consumed hit is accounted for exactly."""
        inst = fused_instance()
        threads, errs = [], []
        per_thread, calls, keys = 20, 6, 4

        def worker(t):
            try:
                for c in range(calls):
                    reqs = [req(f"cons{i % keys}")
                            for i in range(per_thread)]
                    rs = inst.get_rate_limits(reqs, now_ms=NOW + c)
                    assert all(r.error == "" for r in rs)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        try:
            for t in range(16):
                th = threading.Thread(target=worker, args=(t,))
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=120)
            assert not errs, errs
            total = 16 * per_thread * calls
            queries = [req(f"cons{i}", hits=0) for i in range(keys)]
            rs = inst.get_rate_limits(queries, now_ms=NOW + 100)
            consumed = sum(1_000_000 - r.remaining for r in rs)
            assert consumed == total, (consumed, total)
        finally:
            inst.close()


class TestMeshFusedScatter:
    def mesh_inst(self, monkeypatch, **cfg):
        monkeypatch.delenv("GUBER_ENGINE", raising=False)
        monkeypatch.delenv("GUBER_STEP_IMPL", raising=False)
        monkeypatch.setenv("GUBER_MESH_GLOBAL_CAP", "256")
        d = dict(cache_size=1 << 12, sweep_interval_ms=0,
                 engine="pallas", global_mode="mesh", batch_rows=64,
                 behaviors=BehaviorConfig(global_sync_wait_ms=100))
        d.update(cfg)
        return V1Instance(Config(**d), mesh=make_mesh(n=8))

    def g(self, key, hits=2):
        return req(key, name="mf", hits=hits, limit=100_000,
                   behavior=Behavior.GLOBAL)

    def drive(self, inst, waves=3, keys=5):
        for w in range(waves):
            out = inst.get_rate_limits_wire(
                ser([self.g(f"k{i % keys}") for i in range(4 * keys)]),
                now_ms=NOW + 1 + w)
            assert out  # serves, no exception

    def test_fused_scatter_serves_and_conserves(self, monkeypatch):
        """Mesh rows serve INSIDE the fused wave (mesh_fused_hits
        grows; the separate meshglobal dispatch is gone) and the fold's
        conservation oracle stays exact."""
        inst = self.mesh_inst(monkeypatch)
        try:
            # mesh mode pre-builds + binds the tier at construction
            # (the warmup contract) — waves are fusable from wave one
            assert inst.engine.mesh_bound
            self.drive(inst)
            assert inst.engine.mesh_fused_hits == 3 * 20 * 2
            inst._mesh_reconcile_tick()
            mge = inst._meshglobal
            mge.drain()
            s = mge.stats()
            assert s["folded_hits"] == s["injected_hits"] == 120, s
            gm = inst.global_manager
            assert not gm._hits and not gm._hits_raw  # zero gRPC lanes
        finally:
            inst.close()

    def test_fused_ab_identical_vs_grpc_mode(self, monkeypatch):
        """12_mesh_global's ab_identical pin over the FUSED engine:
        mesh-mode responses byte-equal the grpc-mode (sharded) path on
        identical seeded GLOBAL traffic."""
        mi = self.mesh_inst(monkeypatch)
        gi = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0,
                               batch_rows=64),
                        mesh=make_mesh(n=8))
        try:
            datas = [ser([self.g(f"k{i % 5}") for i in range(20)])
                     for _ in range(3)]
            m = [mi.get_rate_limits_wire(d, now_ms=NOW + 1 + i)
                 for i, d in enumerate(datas)]
            g = [gi.get_rate_limits_wire(d, now_ms=NOW + 1 + i)
                 for i, d in enumerate(datas)]
            assert m == g
        finally:
            mi.close()
            gi.close()

    def test_conservation_under_psum_and_device_step_faults(
            self, monkeypatch):
        """The chaos pin: a failing fold (global_psum) swaps back and
        loses nothing; a device_step fault fails its wave BEFORE any
        state moved (nothing applied → nothing injected); after
        recovery folded == injected exactly."""
        inst = self.mesh_inst(monkeypatch)
        try:
            self.drive(inst, waves=2)
            inst.faults.arm("global_psum:error", seed=7)
            inst._mesh_reconcile_tick()  # fold aborts, swap-back
            assert inst.metrics.mesh_global_fold_errors._value.get() \
                >= 1
            self.drive(inst, waves=1)  # hits keep accumulating
            inst.faults.arm("device_step:error", seed=7)
            with pytest.raises(Exception):
                self.drive(inst, waves=1)  # wave dies pre-application
            inst.faults.clear()
            self.drive(inst, waves=1)
            inst._mesh_reconcile_tick()  # clean fold recovers all
            mge = inst._meshglobal
            mge.drain()
            s = mge.stats()
            # 4 successful waves × 20 rows × 2 hits; the faulted wave
            # applied nothing and injected nothing
            assert s["folded_hits"] == s["injected_hits"] == 160, s
        finally:
            inst.close()


class TestPhaseCollapse:
    def test_pack_is_stamped_for_fused_engines_with_exact_partition(
            self, clean_env):
        """Fused waves carry a `pack` segment like any engine's (the
        chip showed the host's routing and fill to be the larger part
        of a wave: ISSUE 24 took the suppression out), and the
        wave-time partition stays exact."""
        fi = fused_instance()
        xi = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0,
                               engine="xla"), mesh=make_mesh(n=1))
        try:
            data = ser(seeded_reqs(5))
            for i in range(3):
                fi.get_rate_limits_wire(data, now_ms=NOW + i)
                xi.get_rate_limits_wire(data, now_ms=NOW + i)
            fp = fi.dispatcher.analytics.phases.snapshot()
            xp = xi.dispatcher.analytics.phases.snapshot()
            for ph in (fp, xp):
                assert {"pack", "device", "resolve"} <= set(ph), ph
            text = fi.metrics.render().decode()
            assert 'gubernator_phase_duration_count{phase="pack"}' in text
            for inst in (fi, xi):
                seen = 0
                for ev in inst.recorder.events():
                    if ev.get("kind") == "wave_completed" \
                            and ev.get("phases"):
                        seen += 1
                        drift = abs(sum(ev["phases"].values())
                                    - ev["duration_ms"])
                        assert drift <= 0.01, ev
                        assert set(ev["phases"]) == {
                            "pack", "device", "resolve"}, ev
                assert seen > 0
        finally:
            fi.close()
            xi.close()

    def test_device_tap_feeds_sketch_without_host_tap(self, clean_env):
        """The fused engine's device tap is the sketch's only columnar
        feed (the dispatcher's host-side copies are off): heavy keys
        still surface in /debug/topkeys."""
        inst = fused_instance()
        try:
            assert inst.dispatcher._fused_tap is True
            data = ser([req("hot", hits=3) for _ in range(50)])
            for i in range(2):
                inst.get_rate_limits_wire(data, now_ms=NOW + i)
            ana = inst.dispatcher.analytics
            assert ana.flush()
            snap = ana.topkeys_snapshot()
            kh = hash_key("fs", "hot")
            hot = [k for k in snap["keys"]
                   if int(k["khash"], 16) == int(kh)]
            assert hot and hot[0]["hits"] == 2 * 50 * 3, snap["keys"][:3]
        finally:
            inst.close()
