"""Wave telemetry + flight recorder + stall watchdog (ISSUE 1).

The watchdog tests inject a fake clock and a gated engine — no real
sleeps: a wave "ages" only when the test advances the clock, and
``_watchdog_poll()`` is driven directly."""
import threading

import pytest

from gubernator_tpu.dispatcher import Dispatcher
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.telemetry import FlightRecorder, exc_text
from gubernator_tpu.types import RateLimitRequest

NOW = 1_780_000_000_000


def req(key, **kw):
    d = dict(hits=1, limit=1000, duration=600_000)
    d.update(kw)
    return RateLimitRequest(name="tel", unique_key=key, **d)


# ---- exc_text -----------------------------------------------------------


def test_exc_text_never_empty():
    # the round-5 bug: str(TimeoutError()) == "" made rows undiagnosable
    assert str(TimeoutError()) == ""
    assert exc_text(TimeoutError()) == "TimeoutError()"
    assert exc_text(ValueError("boom")) == "boom"


# ---- flight recorder ----------------------------------------------------


def test_recorder_ring_bounds_and_ordering():
    r = FlightRecorder(capacity=8)
    for i in range(20):
        r.record("tick", i=i)
    evs = r.events()
    assert len(evs) == 8 == len(r)
    # oldest events fell off; the survivors are the newest, in order
    assert [e["i"] for e in evs] == list(range(12, 20))
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs) and seqs[-1] == 20
    assert [e["i"] for e in r.events(limit=3)] == [17, 18, 19]


def test_recorder_events_are_json_safe_and_error_nonempty():
    import json

    r = FlightRecorder()
    r.record("weird", obj=object(), n=3, flag=True, none=None)
    r.record_error("oops", TimeoutError())
    evs = r.events()
    json.dumps(evs)  # must not raise
    assert evs[0]["obj"].startswith("<object object")
    assert evs[1]["error"] == "TimeoutError()"  # never ""


def test_recorder_captures_active_trace_id():
    from gubernator_tpu.tracing import request_context

    r = FlightRecorder()
    tid = "ab" * 16
    with request_context(f"00-{tid}-{'cd' * 8}-01"):
        r.record("in_ctx")
    r.record("out_ctx")
    evs = r.events()
    assert evs[0]["trace"] == tid
    assert evs[1]["trace"] is None


def test_recorder_capacity_validation():
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_recorder_server_side_kind_and_since_seq_filters():
    """ISSUE 4 satellite: events() filters by kind and by seq so the
    daemon can serve ?kind= / ?since_seq= without shipping the ring."""
    r = FlightRecorder()
    for i in range(6):
        r.record("even" if i % 2 == 0 else "odd", i=i)
    assert [e["i"] for e in r.events(kind="odd")] == [1, 3, 5]
    assert [e["i"] for e in r.events(since_seq=4)] == [4, 5]
    assert [e["i"] for e in r.events(kind="even", since_seq=2)] == [2, 4]
    assert [e["i"] for e in r.events(kind="even", limit=1)] == [4]
    assert r.events(kind="nope") == []


def test_recorder_keeps_dict_fields_queryable():
    """The wave_completed `phases` block must survive as a JSON object,
    not a repr string (one level deep; nested values still coerce)."""
    import json

    r = FlightRecorder()
    r.record("wave_completed", phases={"pack": 0.5, "device": 2.0},
             weird={"obj": object()})
    ev = r.events()[0]
    json.dumps(ev)
    assert ev["phases"] == {"pack": 0.5, "device": 2.0}
    assert ev["weird"]["obj"].startswith("<object object")


# ---- dispatcher wave metrics --------------------------------------------


@pytest.fixture()
def engine():
    # the pure-Python referee engine: wave telemetry is engine-agnostic
    # and must be testable without the jax sharded stack
    from gubernator_tpu.oracle import OracleEngine

    return OracleEngine()


def test_wave_histograms_observed_after_dispatch(engine):
    m, rec = Metrics(), FlightRecorder()
    d = Dispatcher(engine, metrics=m, recorder=rec)
    try:
        r = d.check_batch([req("a"), req("b")], NOW)
        assert len(r) == 2
    finally:
        d.close()
    text = m.render().decode()
    assert "gubernator_dispatcher_wave_size_count 1.0" in text
    assert "gubernator_dispatcher_wave_duration_count 1.0" in text
    # the wave is over: in-flight returned to 0, no stall
    assert "gubernator_dispatcher_waves_in_flight 0.0" in text
    assert "gubernator_dispatcher_stalled 0.0" in text
    assert "gubernator_dispatcher_first_wave_seconds" in text
    kinds = [e["kind"] for e in rec.events()]
    assert kinds == ["wave_launched", "wave_completed", "first_wave"]
    stats = d.debug_stats()
    assert stats["waves"] == 1 and stats["timeouts"] == 0
    assert stats["first_wave_s"] is not None


def test_queue_wait_observed_for_queued_wave(engine):
    m = Metrics()
    d = Dispatcher(engine, metrics=m)
    # callers submit jobs and the worker coalesces them into waves
    threads = [threading.Thread(
        target=lambda i=i: d.check_batch([req(f"q{i}")], NOW))
        for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    d.close()
    text = m.render().decode()
    # every queued job contributed one queue-wait sample
    import re

    count = float(re.search(
        r"gubernator_dispatcher_queue_wait_count (\S+)", text).group(1))
    assert count == 3.0
    snap = d.telemetry_snapshot()
    assert snap["queue_wait_p50_ms"] is not None
    assert snap["wave_size_p50"] >= 1


def test_engine_error_recorded_as_wave_error(engine):
    rec = FlightRecorder()
    d = Dispatcher(engine, recorder=rec)

    def boom(reqs, now):
        raise RuntimeError("device on fire")

    d.engine = type("E", (), {"check_batch": staticmethod(boom)})()
    try:
        with pytest.raises(RuntimeError, match="device on fire"):
            d.check_batch([req("x")], NOW)
    finally:
        d.close()
    errs = [e for e in rec.events() if e["kind"] == "wave_error"]
    assert errs and errs[0]["error"] == "device on fire"


# ---- per-phase latency attribution (ISSUE 4) ----------------------------


def _phase_sums(text):
    import re

    out = {}
    for ph, v in re.findall(
            r'gubernator_phase_duration_sum\{phase="(\w+)"\} (\S+)',
            text):
        out[ph] = float(v)
    return out


def test_phase_histograms_partition_wave_duration(engine):
    """ISSUE 4 acceptance: pack + device + resolve sum to the existing
    wave_duration (same clock, marks stamp segment ends), over lone
    AND coalesced waves."""
    from gubernator_tpu.analytics import KeyAnalytics

    m, rec = Metrics(), FlightRecorder()
    ka = KeyAnalytics(metrics=m)
    d = Dispatcher(engine, metrics=m, recorder=rec, analytics=ka)
    try:
        for i in range(4):  # a call alone in its wave
            d.check_batch([req(f"p{i}")], NOW + i)
        # concurrent callers: coalesced waves
        threads = [threading.Thread(
            target=lambda i=i: d.check_batch([req(f"pq{i}")], NOW))
            for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        d.close()
        ka.close()
    import re

    text = m.render().decode()
    sums = _phase_sums(text)
    wave_sum = float(re.search(
        r"gubernator_dispatcher_wave_duration_sum (\S+)", text).group(1))
    in_wave = sums["pack"] + sums["device"] + sums["resolve"]
    assert in_wave == pytest.approx(wave_sum, rel=1e-6, abs=1e-9)
    # queue_wait mirrors the dispatcher's own histogram sample count
    qw = float(re.search(
        r'gubernator_phase_duration_count\{phase="queue_wait"\} (\S+)',
        text).group(1))
    qw_disp = float(re.search(
        r"gubernator_dispatcher_queue_wait_count (\S+)", text).group(1))
    assert qw == qw_disp == 7.0  # every call waits in the queue
    # the per-wave breakdown rode the flight-recorder events and sums
    # to each wave's duration
    for ev in rec.events(kind="wave_completed"):
        ph = ev["phases"]
        assert set(ph) == {"pack", "device", "resolve"}
        assert sum(ph.values()) == pytest.approx(ev["duration_ms"],
                                                 abs=0.002)


def test_phase_histogram_without_analytics_attached(engine):
    """Phase attribution must not require the analytics subsystem: a
    dispatcher with metrics but analytics=None still feeds the
    histograms (and nothing crashes on the tap paths)."""
    m = Metrics()
    d = Dispatcher(engine, metrics=m)
    try:
        d.check_batch([req("na")], NOW)
    finally:
        d.close()
    sums = _phase_sums(m.render().decode())
    assert set(sums) >= {"pack", "device", "resolve"}


def test_wave_error_still_recorded_with_marks(engine):
    """An engine raise mid-wave (after the pack mark) must not break
    phase segmentation on the error path."""
    from gubernator_tpu.analytics import KeyAnalytics

    ka = KeyAnalytics(metrics=None)
    rec = FlightRecorder()
    d = Dispatcher(engine, recorder=rec, analytics=ka)

    def boom(reqs, now):
        raise RuntimeError("mid-wave")

    d.engine = type("E", (), {"check_batch": staticmethod(boom)})()
    try:
        with pytest.raises(RuntimeError, match="mid-wave"):
            d.check_batch([req("x")], NOW)
    finally:
        d.close()
        ka.close()
    errs = rec.events(kind="wave_error")
    assert errs and errs[0]["error"] == "mid-wave"


# ---- stall watchdog (fake clock, no real sleeps) ------------------------


class FakeClock:
    def __init__(self, t0=1000.0):
        self.t = t0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


class GatedEngine:
    """check_batch blocks until released — the injected slow engine."""

    def __init__(self):
        self.entered = threading.Event()
        self.release = threading.Event()

    def check_batch(self, reqs, now):
        self.entered.set()
        assert self.release.wait(timeout=60)
        from gubernator_tpu.types import RateLimitResponse

        return [RateLimitResponse() for _ in reqs]


def test_watchdog_flags_stall_and_recovers(monkeypatch):
    # threshold 0 → no background watchdog thread: the test owns every
    # poll, so the flag/no-reflag assertions are race-free by design
    monkeypatch.setenv("GUBER_STALL_THRESHOLD_S", "0")
    clock = FakeClock()
    eng = GatedEngine()
    m, rec = Metrics(), FlightRecorder()
    d = Dispatcher(eng, metrics=m, recorder=rec, clock=clock)
    d._stall_threshold_s = 30.0  # re-arm for manual polling
    caller = threading.Thread(target=lambda: d.check_batch([req("s")],
                                                           NOW))
    caller.start()
    assert eng.entered.wait(timeout=30)  # wave is in flight (inline)
    try:
        # below threshold: no stall
        clock.advance(29.0)
        assert d._watchdog_poll() is False
        assert d.debug_stats()["stalled"] is False
        # past threshold: flagged exactly once
        clock.advance(2.0)
        assert d._watchdog_poll() is True
        assert d._watchdog_poll() is False  # no re-flag
        text = m.render().decode()
        assert "gubernator_dispatcher_stalled 1.0" in text
        assert "gubernator_dispatcher_stall_events_total 1.0" in text
        stats = d.debug_stats()
        assert stats["stalled"] is True
        assert stats["oldest_wave_age_s"] >= 31.0
        stall = [e for e in rec.events() if e["kind"] == "wave_stalled"]
        assert len(stall) == 1
        assert "stall threshold" in stall[0]["error"]
        assert stall[0]["age_s"] >= 31.0
    finally:
        eng.release.set()
        caller.join(timeout=60)
    # wave completed → gauge clears (wave_end path, no poll needed)
    assert "gubernator_dispatcher_stalled 0.0" in m.render().decode()
    assert d.debug_stats()["stalled"] is False
    assert d.debug_stats()["stall_events"] == 1
    d.close()


def test_watchdog_threshold_env_override_and_disable(engine, monkeypatch):
    monkeypatch.setenv("GUBER_STALL_THRESHOLD_S", "5")
    d = Dispatcher(engine)
    assert d._stall_threshold_s == 5.0 and d._watchdog is not None
    d.close()
    monkeypatch.setenv("GUBER_STALL_THRESHOLD_S", "0")
    d = Dispatcher(engine)
    assert d._watchdog is None  # disabled
    d.close()
    monkeypatch.delenv("GUBER_STALL_THRESHOLD_S")
    monkeypatch.setenv("GUBER_RESULT_TIMEOUT_S", "40")
    d = Dispatcher(engine)
    # default scales down with a tightened caller timeout (40/4)
    assert d._stall_threshold_s == pytest.approx(10.0)
    d.close()


# ---- caller-timeout diagnosis -------------------------------------------


def test_timeout_error_is_diagnosed_and_counted(monkeypatch):
    from concurrent.futures import TimeoutError as FuturesTimeout

    monkeypatch.setenv("GUBER_RESULT_TIMEOUT_S", "0.2")
    eng = GatedEngine()
    m, rec = Metrics(), FlightRecorder()
    d = Dispatcher(eng, metrics=m, recorder=rec)
    try:
        with pytest.raises(FuturesTimeout) as ei:
            d.check_batch([req("t")], NOW)
    finally:
        eng.release.set()
    msg = str(ei.value)
    assert msg, "timeout error must never str() empty"
    assert "timed out after" in msg and "queue_depth=" in msg
    assert "GUBER_RESULT_TIMEOUT_S" in msg
    assert d.debug_stats()["timeouts"] == 1
    assert "gubernator_dispatcher_wave_timeouts_total 1.0" \
        in m.render().decode()
    tmo = [e for e in rec.events() if e["kind"] == "wave_timeout"]
    assert tmo and tmo[0]["error"]
    d.close()
