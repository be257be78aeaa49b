#!/usr/bin/env python3
"""One cell, once, as a new process:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts ONE in-process gubernator daemon on the attached TPU, restores the
configuration's resident rows, drives the raw-bytes gRPC front door from
generator child processes (which never import JAX and are pinned to
cores of their own), measures from the client's side, checks every
answer, and prints the contract's one JSON line last.  Everything a
cell needs is found by name from ``BENCHMARK.json``: the configuration
in ``benchmark/configs/``, the traffic mix in ``benchmark/traffic/``,
each per-layer metric's reader in ``benchmark/layer_metrics/``.

``--sweep`` (a builder's tool, not the driver's command) keeps one
set-up and runs the open mix at several rates and arrival processes.
``--cpu-rehearsal`` runs the same flow at tiny sizes on the CPU backend;
it names its device and is for ``benchmark/tests`` only.
"""
from __future__ import annotations

T_START = __import__("time").monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
import urllib.request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

CHILD_WAIT_S = 300.0
#: part of the window a --trace 1 run records with the profiler
TRACE_FROM, TRACE_SECONDS = 0.3, 3.0


def say(msg: str) -> None:
    print(f"[{time.monotonic() - T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


# ---- what a cell is made of ---------------------------------------------

def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def load_cell(name: str, rehearsal: bool) -> dict:
    from benchmark.harness import traffic as tr

    manifest = load_json(REPO, "BENCHMARK.json")
    cell = next((w for w in manifest["workloads"] if w["name"] == name),
                None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    config = load_json(REPO, cfg_entry["file"])
    traffic = tr.load(cell["traffic"])
    if rehearsal:
        config = merge(config, config.get("rehearsal", {}))
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    mine = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return {
        "name": name, "chips": cell["chips"], "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
        "per_layer": [m for m in manifest["per_layer"] if mine(m)],
    }


def build_native() -> None:
    """ops/_native*.so is a build output: build it from _native.cpp when
    missing, before JAX is touched, and fail if that cannot be done."""
    try:
        from gubernator_tpu.ops import _native  # noqa: F401
        return
    except ImportError:
        pass
    say("building gubernator_tpu/ops/_native")
    subprocess.run([sys.executable, "gubernator_tpu/ops/setup_native.py",
                    "build_ext", "--inplace"], cwd=REPO, check=True,
                   stdout=subprocess.DEVNULL)
    from gubernator_tpu.ops import _native  # noqa: F401


def core_split() -> tuple[list, list]:
    """(server cores, generator cores): the generators get a quarter of
    the cores this process may use, at most 4, and the daemon the rest —
    so a busy server cannot starve the clock that times it."""
    cores = sorted(os.sched_getaffinity(0))
    n_gen = min(4, max(1, len(cores) // 4))
    if len(cores) < 2:
        return cores, cores
    return cores[:-n_gen], cores[-n_gen:]


# ---- generator children -------------------------------------------------

class Gen:
    def __init__(self, spec: dict):
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_", "GUBER_"))}
        self.p = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "harness", "gen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=REPO, env=env)
        self.index = spec["index"]
        self._send(spec)
        self.jax_imported = False
        self.recv()

    def _send(self, obj: dict) -> None:
        self.p.stdin.write(json.dumps(obj) + "\n")
        self.p.stdin.flush()

    def send(self, cmd: str, **kw) -> None:
        self._send({"cmd": cmd, **kw})

    def recv(self, timeout: float = CHILD_WAIT_S) -> dict:
        box: list = []
        t = threading.Thread(
            target=lambda: box.append(self.p.stdout.readline()),
            daemon=True)
        t.start()
        t.join(timeout)
        if not box or not box[0]:
            raise RuntimeError(f"generator {self.index} gave no answer "
                               f"(exit code {self.p.poll()})")
        out = json.loads(box[0])
        self.jax_imported |= bool(out.get("jax_imported"))
        return out

    def ask(self, cmd: str, timeout: float = CHILD_WAIT_S, **kw) -> dict:
        self.send(cmd, **kw)
        return self.recv(timeout)

    def close(self) -> None:
        try:
            if self.p.poll() is None:
                self.send("quit")
                self.p.wait(timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            pass
        finally:
            if self.p.poll() is None:
                self.p.kill()
            self.p.wait()
            for f in (self.p.stdin, self.p.stdout):
                try:
                    f.close()
                except OSError:
                    pass


# ---- the daemon, seen from outside --------------------------------------

class Front:
    def __init__(self, http_addr: str):
        self.http = f"http://{http_addr}"

    def text(self, path: str) -> str:
        with urllib.request.urlopen(self.http + path, timeout=60) as f:
            return f.read().decode()

    def json(self, path: str):
        return json.loads(self.text(path))

    def metrics(self) -> dict:
        out = {}
        for line in self.text("/metrics").splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.rpartition(" ")
                out[name] = float(val)
        return out


def load_records(paths: list) -> dict:
    import numpy as np

    parts = [dict(np.load(p)) for p in paths]
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class Cell:
    """One daemon lifetime: set-up, windows, checks."""

    def __init__(self, cell: dict, seed: int, rehearsal: bool, trace: bool):
        self.cell, self.seed, self.rehearsal = cell, seed, rehearsal
        self.trace = trace
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.pop = self.config["populations"][self.traffic["population"]]
        self.is_global = "global_fold_errors" in self.config["guarantees"]
        self.run_dir = os.path.join(REPO, ".bench_run", cell["name"])
        shutil.rmtree(self.run_dir, ignore_errors=True)
        os.makedirs(self.run_dir)
        self.gens: list = []
        self.daemon = None
        self.spans = None
        self.compiles: list = []  # monotonic time of each backend compile
        #: virtual epoch-ms of the first window's start, a day ahead of
        #: the wall clock (see rows.py)
        self.v0 = (int(time.time()) + 86_400) * 1000
        #: resident rows are made while JAX and the daemon start
        self._columns: dict = {}
        self._columns_thread = threading.Thread(target=self._make_columns)
        self._columns_thread.start()

    def _make_columns(self) -> None:
        from benchmark.harness import rows

        for name, pop in self.config["populations"].items():
            if pop.get("restore"):
                self._columns[name] = rows.snapshot_columns(
                    pop, self.seed, self.v0)

    # -- set-up ------------------------------------------------------------

    def start_generators(self, gen_cores: list) -> None:
        for g in range(self.traffic["generators"]):
            self.gens.append(Gen({
                "index": g, "seed": self.seed, "traffic": self.traffic,
                "population": self.pop, "cores": gen_cores}))

    def start_daemon(self, mesh) -> None:
        from gubernator_tpu.config import DaemonConfig
        from gubernator_tpu.daemon import spawn_daemon
        from gubernator_tpu.netutil import free_port

        self.grpc_addr = f"127.0.0.1:{free_port()}"
        http_addr = f"127.0.0.1:{free_port()}"
        t0 = time.monotonic()
        self.daemon = spawn_daemon(DaemonConfig(
            grpc_listen_address=self.grpc_addr,
            http_listen_address=http_addr, **self.config["daemon"]),
            mesh=mesh)
        self.front = Front(http_addr)
        sv = self.front.json("/healthz")["serving"]
        say(f"daemon up in {time.monotonic() - t0:.1f}s: {sv}")
        if not sv["native_wire_lane"]:
            raise RuntimeError("the native wire lane is not built")
        if not self.rehearsal and sv["engine"] != self.config["engine"]:
            raise RuntimeError(f"engine {sv['engine']!r}, the configuration "
                               f"states {self.config['engine']!r}")

    def restore_rows(self) -> None:
        """Every population marked ``restore`` is placed with the
        engine's own snapshot-restore path (no request is served)."""
        inst = self.daemon.instance
        t0 = time.monotonic()
        self._columns_thread.join()
        for name, cols in self._columns.items():
            pop = self.config["populations"][name]
            t1 = time.monotonic()
            with inst._engine_mu:
                placed = inst.engine.restore(cols)
            say(f"population {name}: {placed} of {pop['keys']} rows "
                f"restored (waited {t1 - t0:.1f}s for the columns, restore "
                f"{time.monotonic() - t1:.1f}s)")
            if placed != pop["keys"]:
                raise RuntimeError(f"only {placed} of {pop['keys']} rows "
                                   "found a slot")

    def warm(self) -> None:
        """The cell's own shapes, once: its call shape from every caller
        at once, and the sweep the daemon runs every 30 s."""
        from benchmark.harness import wire

        inst = self.daemon.instance
        with inst._engine_mu:
            inst.engine.sweep(int(time.time() * 1000))
        for g in self.gens:
            g.send("connect", addr=self.grpc_addr)
        for g in self.gens:
            g.recv()
        stamp = self.v0 - max(1_000_000, 10 * self.pop["duration_ms"])
        # one call alone first (first touches pin and compile), then
        # every caller at once (coalesced wave widths)
        for gens, alone in ((self.gens[:1], True), (self.gens, False)):
            for g in gens:
                g.send("warm", stamp=stamp, alone=alone)
            bad = [b for g in gens for b in g.recv(600.0)["bad"]]
            if bad:
                raise RuntimeError(f"warm-up failed: {bad[:3]}")
        if self.pop.get("behavior", 0) & wire.BEHAVIOR_GLOBAL:
            self.wait_folded()
        self.front.json("/healthz")

    def wait_folded(self, timeout: float = 60.0) -> dict:
        end = time.monotonic() + timeout
        while True:
            m = self.front.json("/debug/audit")["lanes"]["mesh"]
            if m["injected"] == m["folded"]:
                return m
            if time.monotonic() > end:
                raise RuntimeError(f"the mesh lane did not fold: {m}")
            time.sleep(0.05)

    # -- one measured window -----------------------------------------------

    def window(self, seconds: float, tag: str, v0: int,
               override: dict | None = None, lead: float = 1.0) -> dict:
        import jax

        start_at = time.monotonic() + lead
        paths = []
        for g in self.gens:
            path = os.path.join(self.run_dir, f"{tag}.{g.index}.npz")
            paths.append(path)
            g.send("window", start_at=start_at, seconds=seconds, v0=v0,
                   out=path, **({"override": override} if override else {}))
        w = {"start_at": start_at, "end": start_at + seconds,
             "seconds": seconds, "v0": v0}
        from benchmark.harness.gen import Heartbeat

        beat = Heartbeat()
        time.sleep(max(0.0, start_at - time.monotonic()))
        w["m0"] = self.front.metrics()
        if self.trace:
            trace_dir = os.path.join(self.run_dir, f"trace.{tag}")
            time.sleep(seconds * TRACE_FROM)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # it would slow the host it times
            opts.host_tracer_level = 2  # keeps the TraceAnnotation spans
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            # both scrapes while the profiler records: what is read
            # between them is set against what the trace holds
            w["tm0"] = self.front.metrics()
            time.sleep(min(TRACE_SECONDS, seconds * 0.4))
            w["tm1"] = self.front.metrics()
            jax.profiler.stop_trace()
            w["trace_dir"] = trace_dir
        stale = []
        while (left := w["end"] - time.monotonic()) > 0:
            time.sleep(min(left, 2.0))
            if self.is_global:  # the gauge holds the last fold's reading
                stale.append(self.front.metrics().get(
                    "gubernator_mesh_global_staleness_seconds", 0.0))
        w["staleness_s"] = max(stale, default=0.0)
        w["m1"] = self.front.metrics()
        outs = [g.recv(seconds + CHILD_WAIT_S) for g in self.gens]
        w["response_errors"] = sum(o["response_errors"] for o in outs)
        w["stalls"] = {"server": beat.stop(start_at),
                       **{f"generator{g.index}": o["stalls"]
                          for g, o in zip(self.gens, outs)}}
        if any(w["stalls"].values()):
            say(f"threads woke > 50 ms late (s into the window, s): "
                f"{ {k: v for k, v in w['stalls'].items() if v} }")
        w["rec"] = load_records(paths)
        w["compiles"] = sum(1 for t in self.compiles
                            if w["start_at"] <= t <= w["end"])
        return w

    def replay(self, v_start: int) -> dict:
        """A single caller's seeded stream, after the window."""
        n = self.traffic["requests_per_call"]
        calls = max(30, min(400, 30_000 // n))
        step = max(1, self.pop["duration_ms"] * 21 // (10 * calls))
        path = os.path.join(self.run_dir, "replay.npz")
        self.gens[0].ask("replay", calls=calls, step_ms=step,
                         v_start=v_start, out=path)
        return load_records([path])

    # -- the end -----------------------------------------------------------

    def close(self) -> None:
        self._columns_thread.join()
        self._columns.clear()
        for g in self.gens:
            g.close()
        if self.spans is not None:
            self.spans.remove()
        if self.daemon is not None:
            self.daemon.close()
            self.daemon = None
        shutil.rmtree(self.run_dir, ignore_errors=True)


# ---- correctness and guarantees -----------------------------------------

def judge(c: Cell, w: dict, replay_rec: dict) -> tuple[bool, list, dict]:
    """Every number compared, beside its limit → (correct, lines, parts)."""
    from benchmark.harness import check, scrape

    pop, g = c.pop, c.config["guarantees"]
    rec = w["rec"]
    failed = int((~(rec["ok"] & (rec["answered"] == rec["n"]))).sum())
    answers = check.expand(rec)
    win = check.window_violations(answers, pop, c.seed, w["v0"])
    rep = check.replay_mismatches(replay_rec, pop)
    m1 = w["m1"]
    delta = lambda prefix, has="": scrape.delta(  # noqa: E731
        w["m0"], m1, prefix, has)
    events = sum(len(c.front.json(f"/debug/events?kind={k}")["events"])
                 for k in ("wave_error", "wave_stalled", "wave_timeout",
                           "mesh_degraded", "degraded", "engine_fallback"))
    pb2 = delta("gubernator_wire_lane_requests_total", "pb2")
    rows = [
        ("failed_calls", failed, g["failed_calls"]),
        ("responses_with_error", w["response_errors"], 0),
        ("window_violations", win["violations"], 0),
        ("replay_mismatches", rep["mismatches"], 0),
        ("compiles_in_window", w["compiles"], g["compiles_in_window"]),
        ("wave_error_stalled_timeout_events", events
         + delta("gubernator_dispatcher_wave_timeouts_total")
         + delta("gubernator_dispatcher_stall_events_total"),
         g["wave_error_stalled_timeout_events"]),
        ("pb2_lane_requests_in_window", pb2,
         g["pb2_lane_requests_in_window"]),
    ]
    floors = [("window_answers_checked", win["answers"], 1),
              ("replay_answers_compared", rep["compared"], 1)]
    if "global_fold_errors" in g:
        lane = w["mesh_lane"]
        rows += [
            ("global_hits_injected_minus_folded",
             lane["injected"] - lane["folded"], 0),
            ("global_fold_errors",
             delta("gubernator_mesh_global_fold_errors_total"),
             g["global_fold_errors"]),
            ("global_tier_degraded",
             m1.get("gubernator_mesh_global_degraded", 0.0), 0),
            ("global_staleness_ms",
             1000.0 * w["staleness_s"], g["global_staleness_at_most_ms"]),
        ]
        # the tier counts a request's hits before it decides, so every
        # answered request injects them, over its limit or not
        sent = pop["hits"] * len(answers["status"])
        rows.append(("global_hits_injected_minus_hits_answered",
                     abs(lane["injected"] - w["mesh_lane0"]["injected"]
                         - sent), 0))
        floors.append(("global_folds_in_window",
                       delta("gubernator_mesh_global_folds_total"), 1))
    lines, ok = [], True
    for name, got, limit in rows:
        good = got <= limit
        ok &= good
        lines.append(f"check {name}: {got} (limit: at most {limit}) "
                     f"{'ok' if good else 'NOT CORRECT'}")
    for name, got, limit in floors:
        good = got >= limit
        ok &= good
        lines.append(f"check {name}: {got} (limit: at least {limit}) "
                     f"{'ok' if good else 'NOT CORRECT'}")
    if win.get("by_rule"):
        lines.append(f"check window_violations by rule: {win['by_rule']}")
    lines.append(
        f"checked: {win.get('lifetimes', 0)} bucket lifetimes in the window "
        f"({win.get('restored_lifetimes', 0)} of restored rows), "
        f"{win.get('over_limit_answers', 0)} OVER_LIMIT answers; the replay "
        f"crossed the limit {rep['reference_over_limit']} times")
    return ok, lines, {"window": win, "replay": rep, "failed": failed}


# ---- metrics ------------------------------------------------------------

def layer_reader(name: str):
    path = os.path.join(HERE, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def result_metrics(c: Cell, w: dict, setup_s: float, devices) -> dict:
    from benchmark.harness import e2e, tracered
    from benchmark.harness.spans import SITES

    out = {}
    if not c.trace:
        for m in c.cell["end_to_end"]:
            v = setup_s if m["name"] == "setup_s" else \
                e2e.METRICS[m["name"]](w, c.traffic)
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out
    events = tracered.load_xplane(w["trace_dir"])
    w["trace"] = tracered.reduce(events, span_names=list(SITES))
    ctx = dict(w, spans=c.spans, traffic=c.traffic, config=c.config,
               device_kind=devices[0].device_kind,
               memory_peak_bytes=memory_peak(devices))
    for m in c.cell["per_layer"]:
        v = layer_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---- entry --------------------------------------------------------------

def set_up(args, cell: dict):
    """Everything before the first measured request → (Cell, devices)."""
    rehearsal = args.cpu_rehearsal
    config = cell["config"]
    for k in [k for k in os.environ if k.startswith("GUBER_")]:
        os.environ.pop(k)  # the configuration chooses; nothing inherited
    os.environ.update(config.get("env", {}))
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    build_native()
    server_cores, gen_cores = core_split()
    c = Cell(cell, args.seed, rehearsal, bool(args.trace))
    try:
        c.start_generators(gen_cores)
        os.sched_setaffinity(0, server_cores)
        say(f"cores: os.cpu_count()={os.cpu_count()} server={server_cores} "
            f"generators={gen_cores}")
        from gubernator_tpu import compilecache

        cache_dir = compilecache.setup()
        import jax

        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: c.compiles.append(time.monotonic())
            if name == "/jax/core/compile/backend_compile_duration"
            else None)
        devices = jax.devices()
        say(f"platform={devices[0].platform} kind={devices[0].device_kind!r} "
            f"devices={len(devices)} compile_cache={cache_dir}")
        if devices[0].platform != "tpu" and not rehearsal:
            raise SystemExit("no TPU: this benchmark measures the chip")
        if len(devices) < cell["chips"]:
            raise SystemExit(f"the cell needs {cell['chips']} chips, JAX "
                             f"shows {len(devices)}")
        from gubernator_tpu.parallel import make_mesh

        devices = devices[:cell["chips"]]
        c.start_daemon(make_mesh(devices=devices))
        c.restore_rows()
        c.warm()
        if c.trace:
            from benchmark.harness.spans import Spans

            c.spans = Spans()
            c.spans.install(c.daemon)
        return c, devices
    except BaseException:
        c.close()
        raise


def run_cell(args) -> int:
    cell = load_cell(args.workload, args.cpu_rehearsal)
    c, devices = set_up(args, cell)
    try:
        is_global = c.is_global
        if is_global:
            lane0 = c.wait_folded()
        w = c.window(args.seconds, "w", c.v0)
        setup_s = w["start_at"] - T_START
        if is_global:
            w["mesh_lane0"], w["mesh_lane"] = lane0, c.wait_folded()
        t_check = time.monotonic()
        # a duration after the window's last bucket has expired
        replay_rec = c.replay(
            c.v0 + int(args.seconds * 1000) + 3 * c.pop["duration_ms"])
        correct, lines, parts = judge(c, w, replay_rec)
        for line in lines:
            print(line)
        say(f"checks took {time.monotonic() - t_check:.1f}s")
        if args.control:
            from benchmark.harness import check

            ctl = check.control_window(w["rec"], c.pop, c.seed, w["v0"],
                                       "float32")
            rep = check.replay_mismatches(replay_rec, c.pop, "float32",
                                          served=False)
            print(f"control (reference in float32 in the program's place): "
                  f"window_violations {ctl['violations']} of "
                  f"{ctl['answers']} answers {ctl.get('by_rule')}, "
                  f"replay_mismatches {rep['mismatches']} of "
                  f"{rep['compared']} — the sound run above: "
                  f"{parts['window']['violations']} and "
                  f"{parts['replay']['mismatches']}")
        metrics = result_metrics(c, w, setup_s, devices)
        if any(g.jax_imported for g in c.gens):
            raise RuntimeError("a generator process imported JAX")
        rec = w["rec"]
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": memory_peak(devices)}
        line = {"correct": bool(correct), "attempted": int(len(rec["ok"])),
                "failed": parts["failed"], "metrics": metrics,
                "device": device}
        if c.trace:
            tr = w["trace"]
            if not tr["devices"] and not args.cpu_rehearsal:
                raise RuntimeError("the trace holds no device operation")
            device["busy_s"] = tr.get("busy_s", 0.0)
            device["window_s"] = tr.get("window_s", 0.0)
            line["breakdown"] = {"device_ops": tr.get("device_ops", []),
                                 "idle_gaps": tr.get("idle_gaps", [])}
        if args.cpu_rehearsal:
            line["rehearsal"] = "CPU, tiny sizes: no number here is a " \
                                "device number"
    finally:
        c.close()
    print(json.dumps(line), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--sweep", action="store_true",
                    help="builder's tool: one set-up, the open mix at "
                         "several rates and arrival processes")
    ap.add_argument("--control", action="store_true",
                    help="builder's tool: after the run's own checks, put "
                         "the lower-precision reference in the program's "
                         "place and print what the comparison makes of it")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend (tests only)")
    args = ap.parse_args()
    if args.seconds is None:
        args.seconds = float(load_json(REPO, "BENCHMARK.json")["run_seconds"])
    if args.sweep:
        from benchmark.harness import sweep

        return sweep.run(args, sys.modules[__name__])
    return run_cell(args)


if __name__ == "__main__":
    sys.exit(main())
