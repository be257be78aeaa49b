"""The daemon's threads account for themselves (ISSUE 37):

- ``tracing.ThreadLedger``: CPU, run-queue wait and wake-ups of every
  thread of the process by ROLE (``tracing.THREAD_ROLES``), read from
  /proc/self/task when /metrics is rendered — every thread a daemon
  starts has a role of its own, totals only grow, their sum is the
  process's CPU, a re-read inside 0.5 s is the read before, every
  walk is one call into the extension, and without /proc or without
  the extension there is nothing;
- ``daemon.DoorPool`` and the phases `door.wait` / `door.recv`: what a
  call waits for between gRPC and the servicer's first line;
- `worker.gap` as a ``TraceAnnotation`` while a profile records;
- the role table matches OBSERVABILITY.md both ways.
"""
import re
import threading
import time

import pytest
from prometheus_client import CollectorRegistry, generate_latest

from gubernator_tpu import daemon as daemon_mod
from gubernator_tpu import tracing
from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.parallel import make_mesh
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.tracing import ThreadLedger, phase, thread_role

pytestmark = pytest.mark.skipif(
    ThreadLedger().read() is None,
    reason="no /proc/self/task/<tid>/schedstat on this kernel")


def ser(n, key="k"):
    m = pb.GetRateLimitsReq()
    for i in range(n):
        q = m.requests.add()
        q.name, q.unique_key = "tl", f"{key}{i}"
        q.hits, q.limit, q.duration = 1, 1_000_000, 600_000
    return m.SerializeToString()


def burn(seconds):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        sum(range(2000))


class Held:
    """A named thread that burns CPU, then stays alive until released
    (the ledger reads live threads)."""

    def __init__(self, name, seconds):
        self.done, self.go = threading.Event(), threading.Event()
        self.thread = threading.Thread(target=self._run, name=name,
                                       args=(seconds,), daemon=True)
        self.thread.start()

    def _run(self, seconds):
        burn(seconds)
        self.done.set()
        self.go.wait(30)

    def release(self):
        self.go.set()
        self.thread.join(timeout=30)


def fresh_ledger():
    led = ThreadLedger()
    led.MIN_INTERVAL_S = 0.0  # every read walks
    return led


def series(text, family, **labels):
    out = 0.0
    for line in text.splitlines():
        if line.startswith(family + "{") or line.startswith(family + " "):
            if all(f'{k}="{v}"' in line for k, v in labels.items()):
                out += float(line.rpartition(" ")[2])
    return out


# ---- roles --------------------------------------------------------------


@pytest.mark.parametrize("kind,name,role", [
    ("py", "device-dispatcher", "worker"),
    ("py", "grpc-handler_7", "handler"),
    ("py", "grpc-client-handler_0", "handler"),
    ("py", "Thread-3 (_serve)", "grpc-serve"),
    ("py", "key-analytics", "analytics"),
    ("py", "tick:global-async-hits", "tick"),
    ("py", "MainThread", "py-other"),
    ("py", "dispatcher-watchdog", "py-other"),
    ("comm", "grpc_global_tim", "native-grpc"),
    ("comm", "event_engine", "native-grpc"),
    ("comm", "tf_XLAEigen", "native-xla"),
    ("comm", "tf_pjrt_thread_", "native-xla"),
    ("comm", "py_xla_execute", "native-xla"),
    ("comm", "futex-default-S", "native-xla"),
    ("comm", "EventFDAsyncWor", "native-xla"),
    ("comm", "python", "native-other"),
])
def test_thread_role_by_name(kind, name, role):
    assert thread_role(kind, name) == role


def test_every_thread_a_started_daemon_owns_has_a_role_of_its_own():
    """No thread of the program lands in `py-other` but the watchdog,
    the HTTP listener (and its request threads) and main."""
    import urllib.request

    import grpc

    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.netutil import free_port

    before = set(threading.enumerate())
    addr = f"127.0.0.1:{free_port()}"
    http = f"127.0.0.1:{free_port()}"
    d = spawn_daemon(DaemonConfig(
        grpc_listen_address=addr, http_listen_address=http,
        client_listen_address=f"127.0.0.1:{free_port()}",
        cache_size=1 << 10), mesh=make_mesh(n=1))
    try:
        ch = grpc.insecure_channel(addr)
        call = ch.unary_unary("/pb.gubernator.V1/GetRateLimits")
        for i in range(16):
            call(ser(3, key=f"r{i}_"), timeout=30)
        ch.close()
        text = urllib.request.urlopen(
            f"http://{http}/metrics", timeout=30).read().decode()
        mine = [t for t in threading.enumerate() if t not in before]
        roles = {t.name: thread_role("py", t.name) for t in mine}
    finally:
        d.close()
    other = {n for n, r in roles.items() if r == "py-other"}
    assert all(n == "dispatcher-watchdog" or n.startswith("http-")
               or "process_request_thread" in n for n in other), other
    assert {"worker", "handler", "grpc-serve", "analytics", "tick"} \
        <= set(roles.values()), roles
    # two servers: two _serve loops; the ledger counted what was alive
    assert sum(r == "grpc-serve" for r in roles.values()) == 2
    for role in ("worker", "handler", "grpc-serve", "analytics", "tick",
                 "py-other"):
        assert series(text, "gubernator_threads", role=role) >= 1, role
        assert series(text, "gubernator_thread_wakeups_total",
                      role=role) >= 1, role
    assert series(text, "gubernator_thread_cpu_seconds_total",
                  role="handler") > 0
    assert series(text, "gubernator_thread_switches_total",
                  role="worker", kind="voluntary") >= 1
    assert series(text, "gubernator_thread_ledger_clock_seconds") > 0
    # the door's phases were observed for the calls door_inflight saw
    for name in ("door.wait", "door.recv"):
        m = re.search(r'gubernator_phase_duration_count\{phase="%s"\} '
                      r'(\S+)' % re.escape(name), text)
        assert m and float(m.group(1)) == 16 // 8, name


# ---- the ledger's arithmetic -------------------------------------------


def test_role_totals_stay_monotone_when_a_thread_exits():
    led = fresh_ledger()
    led.read()
    h = Held("key-analytics", 0.25)
    assert h.done.wait(30)
    alive = led.read()["roles"]["analytics"]
    h.release()
    gone = led.read()["roles"]["analytics"]
    assert alive[0] >= 0.15 and alive[3] >= 1
    assert gone[0] >= alive[0] and gone[1] >= alive[1] \
        and gone[2] >= alive[2]
    assert gone[3] == alive[3] - 1
    # a thread under the same id later starts from its own zero
    again = led.read()["roles"]["analytics"]
    assert again[:3] == gone[:3]


def test_role_cpu_sums_to_the_process_cpu_over_a_busy_second():
    """Σ roles' Δ CPU is the process's CPU time; and three Python
    threads that never let go of the interpreter use ONE core between
    them — the bound `gil_demand_cores` rests on."""
    led = fresh_ledger()
    r0, p0 = led.read(), time.process_time()
    held = [Held(n, 1.0) for n in ("device-dispatcher", "grpc-handler_0")]
    burn(1.0)
    assert all(h.done.wait(30) for h in held)
    r1, p1 = led.read(), time.process_time()
    for h in held:
        h.release()
    by_role = {r: r1["roles"][r][0] - r0["roles"][r][0]
               for r in r1["roles"]}
    assert sum(by_role.values()) == pytest.approx(p1 - p0, rel=0.10)
    assert by_role["worker"] >= 0.1 and by_role["handler"] >= 0.1
    elapsed = r1["clock"] - r0["clock"]
    assert elapsed >= 1.0
    python = sum(v for r, v in by_role.items()
                 if not r.startswith("native-"))
    assert 0.4 * elapsed <= python <= 1.25 * elapsed, (python, elapsed)
    assert r1["switches"][0] > r0["switches"][0]  # the GIL changed hands


def test_a_reread_inside_half_a_second_is_the_read_before():
    led = ThreadLedger()
    assert led.MIN_INTERVAL_S == 0.5
    first = led.read()
    burn(0.02)
    assert led.read() is first
    m = Metrics()
    clock = lambda: series(m.render().decode(),  # noqa: E731
                           "gubernator_thread_ledger_clock_seconds")
    c0 = clock()
    assert clock() == c0  # two scrapes, one walk
    m.thread_ledger.MIN_INTERVAL_S = 0.0
    assert clock() > c0


def test_the_collector_yields_nothing_without_proc_task(tmp_path):
    led = ThreadLedger(task_dir=str(tmp_path / "absent"))
    assert led.read() is None
    assert list(led.collect()) == []
    reg = CollectorRegistry()
    reg.register(led)
    assert b"gubernator_thread" not in generate_latest(reg)
    # and a /proc whose threads have no schedstat is no better
    (tmp_path / "task" / "1").mkdir(parents=True)
    assert ThreadLedger(task_dir=str(tmp_path / "task")).read() is None


def fake_task_dir(root, threads, schedstat=False, switches=False):
    """A /proc/self/task of ``{tid: (comm, utime ticks, stime ticks)}``:
    `stat` always; `schedstat` and the switch counts only on request —
    what a sandboxed kernel (the chip tool's machines) leaves out."""
    for tid, (comm, utime, stime) in threads.items():
        d = root / str(tid)
        d.mkdir(parents=True, exist_ok=True)
        (d / "comm").write_text(comm + "\n")
        (d / "stat").write_text(
            f"{tid} ({comm} (x) y) S 1 1 1 0 -1 4194304 10 0 0 0 "
            f"{utime} {stime} 0 0 20 0 9 0 100 1000 10 1844 1 1 0 0\n")
        (d / "status").write_text(
            f"Name:\t{comm}\nState:\tS (sleeping)\nThreads:\t9\n" + (
                "voluntary_ctxt_switches:\t7\n"
                "nonvoluntary_ctxt_switches:\t2\n" if switches else ""))
        if schedstat:
            (d / "schedstat").write_text(
                f"{(utime + stime) * 10_000_000} 5000000 40\n")
    return str(root)


def test_a_kernel_without_schedstat_reads_cpu_from_stat(tmp_path):
    """The chip tool's machines: no `schedstat`, no switch counts.  CPU
    comes from `stat` (utime + stime, clock ticks); the run-queue,
    wake-up and switch series are then not exported at all — a reader
    finds nothing rather than zeros."""
    import os

    tick = 1.0 / os.sysconf("SC_CLK_TCK")
    threads = {11: ("event_engine", 30, 20), 12: ("tf_XLAEigen", 100, 0),
               13: ("python", 1, 1)}
    led = ThreadLedger(task_dir=fake_task_dir(tmp_path, threads))
    led.MIN_INTERVAL_S = 0.0
    snap = led.read()
    assert led.source == snap["source"] == "stat"
    assert snap["switches"] is None
    assert snap["roles"]["native-grpc"] == (50 * tick, 0.0, 0, 1)
    assert snap["roles"]["native-xla"] == (100 * tick, 0.0, 0, 1)
    assert snap["roles"]["native-other"] == (2 * tick, 0.0, 0, 1)
    names = {f.name for f in led.collect()}
    assert names == {"gubernator_thread_cpu_seconds", "gubernator_threads",
                     "gubernator_thread_ledger_clock_seconds"}
    # deltas, and a thread that went
    threads[12] = ("tf_XLAEigen", 150, 25)
    del threads[11]
    (tmp_path / "11" / "stat").unlink()
    (tmp_path / "11" / "comm").unlink()
    (tmp_path / "11" / "status").unlink()
    (tmp_path / "11").rmdir()
    fake_task_dir(tmp_path, threads)
    snap = led.read()
    assert snap["roles"]["native-xla"][0] == pytest.approx(175 * tick)
    assert snap["roles"]["native-grpc"] == (50 * tick, 0.0, 0, 0)
    # the same threads on a kernel that has both files
    full = ThreadLedger(task_dir=fake_task_dir(
        tmp_path / "full", threads, schedstat=True, switches=True))
    snap = full.read()
    assert snap["source"] == "schedstat" and snap["switches"] == (0, 0)
    assert snap["roles"]["native-xla"] == (1.75, 0.005, 40, 1)
    assert {f.name for f in full.collect()} >= {
        "gubernator_thread_runq_wait_seconds", "gubernator_thread_wakeups",
        "gubernator_thread_switches"}


def test_without_the_extension_there_is_no_ledger(monkeypatch):
    """No Python loop stands in for `thread_files`: on a loaded daemon
    it waits for the GIL at every file (PERF.md §6, PR 37)."""
    monkeypatch.setattr(tracing, "_thread_files", None)
    led = ThreadLedger()
    assert led.read() is None and list(led.collect()) == []
    assert b"gubernator_thread" not in Metrics().render()


def test_comm_is_one_more_walk_and_only_for_native_threads_not_met(
        tmp_path, monkeypatch):
    walks = []
    real = tracing._thread_files

    def counted(task_dir, name):
        walks.append(name)
        return real(task_dir, name)

    monkeypatch.setattr(tracing, "_thread_files", counted)
    threads = {21: ("grpc_global_tim", 5, 0), 22: ("tf_XLAEigen", 7, 0)}
    led = ThreadLedger(task_dir=fake_task_dir(tmp_path, threads))
    led.MIN_INTERVAL_S = 0.0
    led.read()
    assert walks == ["schedstat", "stat", "comm"]
    led.read()
    assert walks[3:] == ["stat"]  # every role is cached
    threads[23] = ("event_engine", 3, 0)
    fake_task_dir(tmp_path, threads)
    snap = led.read()
    assert walks[4:] == ["stat", "comm"]
    assert snap["roles"]["native-grpc"][3] == 2
    # the live process: its Python threads need no comm at all
    del walks[:]
    live = fresh_ledger()
    live.read()
    live.read()
    assert walks.count("comm") <= 1 and walks.count("schedstat") == 2


def test_a_worker_whose_status_races_its_exit_is_counted_once(tmp_path):
    """A status read that fails for a worker met before takes the CPU
    delta and keeps the switches' last reading: the thread is not new."""
    h = Held("device-dispatcher", 0.0)
    try:
        tid = h.thread.native_id
        threads = {tid: ("python", 300, 0)}
        root = fake_task_dir(tmp_path, threads, schedstat=True,
                             switches=True)
        led = ThreadLedger(task_dir=root)
        led.MIN_INTERVAL_S = 0.0
        snap = led.read()
        assert snap["roles"]["worker"][0] == pytest.approx(3.0)
        assert snap["switches"] == (7, 2)
        threads[tid] = ("python", 350, 0)
        fake_task_dir(tmp_path, threads, schedstat=True, switches=True)
        (tmp_path / str(tid) / "status").unlink()
        snap = led.read()
        assert snap["roles"]["worker"][0] == pytest.approx(3.5)
        assert snap["switches"] == (7, 2)
        (tmp_path / str(tid) / "status").write_text(
            "Name:\tpython\nvoluntary_ctxt_switches:\t19\n"
            "nonvoluntary_ctxt_switches:\t3\n")
        snap = led.read()
        assert snap["roles"]["worker"][0] == pytest.approx(3.5)
        assert snap["switches"] == (19, 3)
    finally:
        h.release()


def test_the_ledgers_series_are_on_the_one_registry():
    text = Metrics().render().decode()
    for family in ("gubernator_thread_cpu_seconds_total",
                   "gubernator_thread_runq_wait_seconds_total",
                   "gubernator_thread_wakeups_total", "gubernator_threads"):
        for role in tracing.THREAD_ROLES:
            assert f'{family}{{role="{role}"}}' in text, (family, role)
    assert 'gubernator_thread_switches_total{kind="voluntary",' \
           'role="worker"}' in text
    assert series(text, "gubernator_threads", role="py-other") >= 1  # main


# ---- door.wait / door.recv ---------------------------------------------


class _Sink:
    def __init__(self):
        self.samples = []
        self.call_sample = 1

    def observe_phase(self, name, seconds, cpu=None, exemplar=None):
        self.samples.append((name, seconds))


class _Context:
    def invocation_metadata(self):
        return ()

    def time_remaining(self):
        return None


def test_door_phases_partition_submit_to_servicer(monkeypatch):
    """`door.wait` covers the time a one-thread pool's first task held
    the thread; `door.wait` + `door.recv` partition submit → the
    servicer's first line exactly (they share the middle reading)."""
    made, seen = [], {}

    class Recording(phase):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    monkeypatch.setattr(daemon_mod, "phase", Recording)
    sink = _Sink()

    class _Inst:
        dispatcher = sink
        metrics = Metrics()
        span_recorder = None

        def get_rate_limits_wire(self, data):
            seen.update(at=tracing._tls.door_at,
                        run_at=tracing._tls.door_run_at,
                        inside=time.perf_counter())
            return b""

    servicer = daemon_mod._V1Servicer(_Inst())
    pool = daemon_mod.DoorPool(max_workers=1,
                               thread_name_prefix="grpc-handler")
    started = threading.Event()
    try:
        first = pool.submit(lambda: (started.set(), time.sleep(0.15)))
        assert started.wait(30)
        before = time.perf_counter()
        pool.submit(servicer.GetRateLimitsWire, b"", _Context()).result(30)
        first.result(30)
    finally:
        pool.shutdown()
    wait, recv = made
    assert (wait.name, recv.name) == ("door.wait", "door.recv")
    assert [n for n, _ in sink.samples] == ["door.wait", "door.recv"]
    assert wait._t0 == seen["at"] >= before
    assert wait.t1 == recv._t0 == seen["run_at"]  # the shared reading
    assert recv.t1 <= seen["inside"]
    assert sink.samples[0][1] >= 0.12  # the first task held the thread
    assert sink.samples[0][1] + sink.samples[1][1] == pytest.approx(
        recv.t1 - wait._t0, abs=1e-9)
    # a servicer that no DoorPool runs records neither
    made.clear()
    t = threading.Thread(target=servicer.GetRateLimitsWire,
                         args=(b"", _Context()), name="direct")
    t.start()
    t.join(timeout=30)
    assert made == []


def test_door_phases_are_sampled_one_call_in_eight():
    sink = _Sink()
    sink.call_sample = 8

    class _Inst:
        dispatcher = sink
        metrics = Metrics()
        span_recorder = None

        def get_rate_limits_wire(self, data):
            return b""

    servicer = daemon_mod._V1Servicer(_Inst())
    with daemon_mod.DoorPool(max_workers=2) as pool:
        for _ in range(24):
            pool.submit(servicer.GetRateLimitsWire, b"",
                        _Context()).result(30)
    assert [n for n, _ in sink.samples] == ["door.wait", "door.recv"] * 3


# ---- worker.gap on the profile's clock ---------------------------------


class _Annotation:
    enabled = False
    log: list = []

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def in_thread(fn):
    box = []
    t = threading.Thread(target=lambda: box.append(fn()), name="gap-test")
    t.start()
    t.join(timeout=30)
    return box[0]


def test_worker_gap_is_annotated_only_while_a_profile_records(monkeypatch):
    monkeypatch.setattr(tracing, "_annotation", _Annotation)
    monkeypatch.setattr(_Annotation, "log", [])
    log = _Annotation.log

    def worker():
        tracing.partition_thread()
        monkeypatch.setattr(_Annotation, "enabled", False)
        phase("wave.begin").begin().end()
        phase("wave.concat").begin().end()
        quiet = list(log)
        monkeypatch.setattr(_Annotation, "enabled", True)
        phase("wave.begin").begin().end()
        # an at=-bounded phase between two of the thread's own leaves
        # the gap open and opens none
        phase("device").begin(at=1.0).end(at=2.0)
        phase("wave.concat").begin().end()
        # a begin AT the cursor shares its boundary: the gap closes
        a = phase("worker.wait").begin()
        a.end()
        phase("worker.coalesce").begin(at=a.t1).end()
        phase("wave.end").begin().end()
        return quiet, tracing.take_gap()

    quiet, gap = in_thread(worker)
    assert quiet == [] and gap > 0
    G = "worker.gap"
    assert log == [
        ("enter", "wave.begin"), ("exit", "wave.begin"), ("enter", G),
        ("enter", "device"), ("exit", "device"),
        ("exit", G), ("enter", "wave.concat"), ("exit", "wave.concat"),
        ("enter", G),
        ("exit", G), ("enter", "worker.wait"), ("exit", "worker.wait"),
        ("enter", G),
        ("exit", G), ("enter", "worker.coalesce"),
        ("exit", "worker.coalesce"), ("enter", G),
        ("exit", G), ("enter", "wave.end"), ("exit", "wave.end"),
        ("enter", G)]


def test_no_gap_annotation_off_a_partition_thread(monkeypatch):
    monkeypatch.setattr(tracing, "_annotation", _Annotation)
    monkeypatch.setattr(_Annotation, "log", [])
    monkeypatch.setattr(_Annotation, "enabled", True)

    def handler():
        phase("ingest").begin().end()
        phase("build").begin().end()

    in_thread(handler)
    assert _Annotation.log == [("enter", "ingest"), ("exit", "ingest"),
                               ("enter", "build"), ("exit", "build")]


# ---- the table is the documented one -----------------------------------


def test_thread_roles_table_matches_the_docs_both_ways():
    from tools.guberlint import docs

    assert docs.thread_roles_doc_problems() == []
    tracing.THREAD_ROLES["bogus"] = ("x", ("bogus",), "not a role")
    try:
        found = docs.thread_roles_doc_problems()
    finally:
        del tracing.THREAD_ROLES["bogus"]
    assert len(found) == 1 and "bogus" in found[0], found
    # and the other way: a role the table documents but the code lost
    saved = dict(tracing.THREAD_ROLES)
    del tracing.THREAD_ROLES["tick"]
    try:
        found = docs.thread_roles_doc_problems()
    finally:
        tracing.THREAD_ROLES.clear()
        tracing.THREAD_ROLES.update(saved)  # in the table's own order
    assert len(found) == 1 and "'tick'" in found[0], found
    assert docs.thread_roles_doc_problems() == []
