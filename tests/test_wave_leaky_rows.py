"""``gubernator_wave_leaky_rows``: the LEAKY_BUCKET rows that entered a
wave's device program, counted once a wave by whatever engine served it
(the benchmark's ``leaky_rows_per_wave`` divides it by the waves).  A
wave of n leaky and m token rows raises it by exactly n, an all-token
wave by 0 — on both entries a wave has into an engine, each counting in
its ``wave.route``: ``check_packed`` (serial waves, the retry lane) and
``launch_packed`` (the dispatcher's pipeline) — and so for a call that
came in through the fused wire ingest (``prepack_wire``), whose rows
reach the engine through ``launch_packed`` like any other's."""
import numpy as np
import pytest

from gubernator_tpu import Algorithm, RateLimitRequest
from gubernator_tpu.config import Config
from gubernator_tpu.core.batch import pack_requests
from gubernator_tpu.hashing import hash_request_keys
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.ops.pallas_step import EFF_BOUND
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.parallel.pallas_engine import PallasServingEngine
from gubernator_tpu.wire import req_to_tlv

NOW = 1_790_000_000_000
ENGINES = {"xla_classic": ShardedEngine, "pallas_fused": PallasServingEngine}


def reqs_of(n_leaky: int, n_token: int, duration=10_000):
    """Leaky and token rows interleaved, two rows a key."""
    out = [RateLimitRequest(
        name="wl", unique_key=f"l{i // 2}", hits=1, limit=600,
        duration=duration, algorithm=Algorithm.LEAKY_BUCKET, burst=600)
        for i in range(n_leaky)]
    tok = [RateLimitRequest(name="wl", unique_key=f"t{i // 2}", hits=1,
                            limit=100, duration=10_000)
           for i in range(n_token)]
    for i, t in enumerate(tok):
        out.insert(min(len(out), 2 * i), t)
    return out


def packed(reqs):
    kh = hash_request_keys([r.name for r in reqs],
                           [r.unique_key for r in reqs])
    batch, errs = pack_requests(reqs, NOW, size=len(reqs), key_hashes=kh)
    assert not any(errs)
    return batch, kh


def wire(reqs) -> bytes:
    return b"".join(req_to_tlv(r) for r in reqs)


@pytest.fixture(scope="module", params=list(ENGINES))
def engine(request):
    eng = ENGINES[request.param](make_mesh(n=1), capacity_per_shard=1 << 10,
                                 batch_per_shard=64)
    eng.metrics_ref = Metrics()
    return eng


def counted(eng) -> float:
    return eng.metrics_ref.wave_leaky_rows._value.get()


def via_check_packed(eng, reqs):
    batch, kh = packed(reqs)
    assert not eng.check_packed(batch, kh, NOW)[4].any()


def via_launch_packed(eng, reqs):
    batch, kh = packed(reqs)
    token = eng.launch_packed(batch, kh, NOW)
    assert not eng.sync_packed(token)[4].any()
    eng.drop_packed(token)


PATHS = {"check_packed": via_check_packed, "launch_packed": via_launch_packed}


@pytest.mark.parametrize("n_leaky,n_token", [(13, 29), (40, 0), (0, 37)])
@pytest.mark.parametrize("path", list(PATHS))
def test_a_wave_counts_its_leaky_rows(engine, path, n_leaky, n_token):
    before = counted(engine)
    PATHS[path](engine, reqs_of(n_leaky, n_token))
    assert counted(engine) - before == n_leaky


@pytest.fixture(scope="module", params=list(ENGINES))
def instance(request):
    eng = ENGINES[request.param](make_mesh(n=1), capacity_per_shard=1 << 10,
                                 batch_per_shard=64)
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      engine=eng)
    yield inst
    inst.close()


@pytest.mark.parametrize("n_leaky,n_token", [(13, 29), (40, 0), (0, 37)])
def test_a_fused_wire_call_counts_its_leaky_rows(instance, n_leaky, n_token):
    """``prepack_wire`` through ``V1Instance.get_rate_limits_wire``: the
    call's rows are counted once, where the worker's wave routes them."""
    m = instance.metrics
    lane = m.wire_lane_counter.labels(lane="wire_local")._value
    before, served = m.wave_leaky_rows._value.get(), lane.get()
    instance.get_rate_limits_wire(wire(reqs_of(n_leaky, n_token)), NOW)
    assert lane.get() - served == n_leaky + n_token  # the fused lane took it
    assert m.wave_leaky_rows._value.get() - before == n_leaky


def test_rows_the_kernel_cannot_represent_are_not_counted():
    """A leaky row outside the kernel's value domain rides its wave
    invalid and is answered unservable: it entered no device program."""
    eng = PallasServingEngine(make_mesh(n=1), capacity_per_shard=1 << 10,
                              batch_per_shard=64)
    eng.metrics_ref = Metrics()
    reqs = reqs_of(6, 4) + reqs_of(3, 0, duration=EFF_BOUND + 5)
    batch, kh = packed(reqs)
    full = eng.check_packed(batch, kh, NOW)[4]
    assert int(full.sum()) == 3 and counted(eng) == 6


def test_the_daemons_registry_carries_it():
    """Through the instance, as the benchmark scrapes it: the wire lane
    on the CPU's serving engine (classic), 11 leaky of 30 rows."""
    inst = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0),
                      mesh=make_mesh(n=1))
    try:
        assert b"gubernator_wave_leaky_rows_total 0.0" in \
            inst.metrics.render()
        inst.get_rate_limits_wire(wire(reqs_of(0, 25)), NOW)
        assert b"gubernator_wave_leaky_rows_total 0.0" in \
            inst.metrics.render()
        inst.get_rate_limits_wire(wire(reqs_of(11, 19)), NOW + 1)
        assert b"gubernator_wave_leaky_rows_total 11.0" in \
            inst.metrics.render()
    finally:
        inst.close()
