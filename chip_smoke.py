#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the service path starts,
serves and answers correctly on a directly attached TPU.

One process holds the chip: a daemon (``spawn_daemon``) and its client
threads.  Two passes run one after the other, each through the daemon's
real front doors (gRPC ``GetRateLimits`` with raw wire bytes, the HTTP
gateway, ``/healthz``, ``/metrics``, ``/debug/*``):

  pass A  the default engine selection (on a TPU it must resolve to
          ``pallas-fused`` — the Mosaic decision kernel), with
          ``GUBER_GLOBAL_MODE=mesh``: 10M distinct TOKEN_BUCKET keys
          made resident in a 2^26-row table, a seeded Zipf(1.1) stream
          and a LEAKY_BUCKET slice compared response-for-response with
          ``gubernator_tpu/oracle.py``, a sweep whose live count must
          equal the keys loaded, GLOBAL traffic folded by the mesh
          collective with exact conservation, the HTTP flow.
  pass B  ``GUBER_ENGINE=xla`` (the classic XLA engine, whose sweep is
          the Pallas sweep kernel) at the same capacity with 1M keys,
          after pass A's daemon is closed and its HBM released.

Every phase that fails raises: the exit code is nonzero and no result
line is printed.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``--cpu-rehearsal`` runs the same phases at tiny sizes on the CPU
backend (the Mosaic kernels in interpret mode) for tier-1; it is chosen
by the caller, never fallen into: without it any platform other than
``tpu`` is a failure.  ``--chips N`` spreads the one table over an
N-device mesh (default 1, whatever the machine shows).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

T_START = time.monotonic()
#: the whole run must end inside the driver's 1200 s, compilation
#: included; loads that would overrun stop early and print `reduced`
BUDGET_S = 1100.0

#: requests per GetRateLimits call — upstream's max batch
BATCH = 1000
LIMIT = 100
DURATION_MS = 10_000


def say(msg: str) -> None:
    print(f"[{time.monotonic() - T_START:7.1f}s] {msg}", flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class Sizes:
    """Data and state sizes: the north-star deployment of BASELINE.json
    (config 3 at 10M keys, config 2's leaky slice, config 4's GLOBAL
    sync), or the tiny CPU rehearsal."""

    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        if rehearsal:
            self.capacity = 1 << 14
            self.keys = {"A": 3_000, "B": 2_000}
            self.batch = 100
            self.zipf_requests = 2_000
            self.leaky_keys, self.leaky_requests = 50, 500
            self.global_keys, self.global_requests = 8, 400
            self.wave_buckets = "128"  # rows per wave, tiny to match
            self.clients = 2
        else:
            self.capacity = 1 << 26
            self.keys = {"A": 10_000_000, "B": 1_000_000}
            self.batch = BATCH
            self.zipf_requests = 100_000
            self.leaky_keys, self.leaky_requests = 1_000, 10_000
            self.global_keys, self.global_requests = 64, 6_400
            self.wave_buckets = ""  # the engine's default widths
            self.clients = 8


def build_native() -> float:
    """ops/_native*.so is a build output (git-ignored): build it from
    _native.cpp when missing — before jax is touched — and fail if that
    is not possible.  Returns the seconds spent."""
    t0 = time.monotonic()
    try:
        from gubernator_tpu.ops import _native  # noqa: F401
        return 0.0
    except ImportError:
        pass
    say("building gubernator_tpu/ops/_native from _native.cpp")
    subprocess.run(
        [sys.executable, "gubernator_tpu/ops/setup_native.py", "build_ext",
         "--inplace"], cwd=REPO, check=True, stdout=subprocess.DEVNULL)
    from gubernator_tpu.ops import _native  # noqa: F401
    return time.monotonic() - t0


class WireTemplate:
    """Vectorized GetRateLimitsReq builder: one request TLV built by
    the repo's own codec (wire.req_to_tlv) is tiled per batch and only
    the key digits and the created_at varint are overwritten."""

    KEY_DIGITS = 10  # hex digits: 2^40 key ids
    _PLACEHOLDER = "#" * KEY_DIGITS

    def __init__(self, np, **fields):
        from gubernator_tpu.types import RateLimitRequest
        from gubernator_tpu.wire import req_to_tlv

        self.np = np
        self.fields = fields
        probe = RateLimitRequest(unique_key=self._PLACEHOLDER,
                                 created_at=1 << 41, **fields)
        tlv = req_to_tlv(probe)
        self.key_off = tlv.index(self._PLACEHOLDER.encode())
        # created_at is the hand-appended LAST field: tag 0x50 + a
        # 6-byte varint for any epoch-ms stamp in [2^35, 2^42)
        self.ts_off = len(tlv) - 6
        check(tlv[self.ts_off - 1] == 0x50, "created_at tag not last")
        self.tlv = np.frombuffer(tlv, np.uint8)
        self._hex = np.frombuffer(b"0123456789abcdef", np.uint8)
        self._shifts = np.arange(self.KEY_DIGITS - 1, -1, -1,
                                 dtype=np.uint64) * np.uint64(4)

    def key_of(self, kid: int) -> str:
        return format(int(kid), f"0{self.KEY_DIGITS}x")

    def batch(self, kids, created_ms: int) -> bytes:
        np = self.np
        check(1 << 35 <= created_ms < 1 << 42, "stamp outside varint6")
        m = np.tile(self.tlv, (len(kids), 1))
        digits = (kids[:, None] >> self._shifts[None, :]) & np.uint64(15)
        m[:, self.key_off:self.key_off + self.KEY_DIGITS] = \
            self._hex[digits.astype(np.int64)]
        v = created_ms
        for i in range(6):
            m[:, self.ts_off + i] = (v & 0x7F) | (0x80 if i < 5 else 0)
            v >>= 7
        return m.tobytes()

    def request(self, kid: int, created_ms: int):
        from gubernator_tpu.types import RateLimitRequest

        return RateLimitRequest(unique_key=self.key_of(kid),
                                created_at=created_ms, **self.fields)


class Front:
    """The daemon's front doors, as a client sees them."""

    def __init__(self, grpc_addr: str, http_addr: str, clients: int):
        import grpc

        self.http = f"http://{http_addr}"
        self._chans = [grpc.insecure_channel(
            grpc_addr, options=[("grpc.use_local_subchannel_pool", 1)])
            for _ in range(clients)]
        self._calls = [c.unary_unary("/pb.gubernator.V1/GetRateLimits")
                       for c in self._chans]

    def get_rate_limits_raw(self, data: bytes, lane: int = 0) -> bytes:
        return self._calls[lane % len(self._calls)](data, timeout=120)

    def get_text(self, path: str) -> str:
        with urllib.request.urlopen(self.http + path, timeout=60) as f:
            return f.read().decode()

    def get_json(self, path: str):
        return json.loads(self.get_text(path))

    def post_json(self, path: str, payload: dict):
        req = urllib.request.Request(
            self.http + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as f:
            return json.loads(f.read())

    def metrics(self) -> dict:
        """/metrics → {'name{labels}': value} (samples only)."""
        out = {}
        for line in self.get_text("/metrics").splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.rpartition(" ")
                out[name] = float(val)
        return out

    def close(self) -> None:
        for c in self._chans:
            c.close()


def msum(metrics: dict, prefix: str) -> float:
    return sum(v for k, v in metrics.items() if k.startswith(prefix))


def parse_responses(data: bytes):
    from gubernator_tpu.proto import gubernator_pb2 as pb

    return pb.GetRateLimitsResp.FromString(data).responses


def same(resp, exp) -> bool:
    return (int(resp.status) == int(exp.status)
            and resp.limit == exp.limit
            and resp.remaining == exp.remaining
            and resp.reset_time == exp.reset_time
            and resp.error == (exp.error or ""))


class Pass:
    """One daemon lifetime: spawn, warm up, serve the phases, close."""

    def __init__(self, label: str, sizes: Sizes, seed: int, mesh,
                 expect_kind: str, global_mesh: bool, np):
        self.label, self.sizes, self.seed = label, sizes, seed
        self.mesh, self.expect_kind = mesh, expect_kind
        self.global_mesh = global_mesh
        self.np = np
        self.reduced: dict = {}
        self.timing: dict = {}
        # virtual time base, a day ahead of the wall clock: every row's
        # expiry then lies in the daemon's future, so its wall-clock
        # sweep sees the loaded keys live while the caller-supplied
        # created_at stamps keep bucket time reproducible
        self.t0 = (int(time.time()) + 86_400) * 1000
        self.tok = WireTemplate(np, name=f"smoke{label}", hits=1,
                                limit=LIMIT, duration=DURATION_MS)

    # -- key ids -----------------------------------------------------------

    def kid(self, i):
        """Distinct 40-bit key id of key index i (odd multiplier mod
        2^40 is a bijection), salted by --seed."""
        np = self.np
        salt = np.uint64((self.seed * 0x9E3779B97F4A7C15) % (1 << 40))
        return ((np.asarray(i, np.uint64) * np.uint64(0x5851F42D4C957F2D)
                 + salt) & np.uint64((1 << 40) - 1))

    # -- lifecycle ---------------------------------------------------------

    def spawn(self):
        from gubernator_tpu.config import BehaviorConfig, DaemonConfig
        from gubernator_tpu.daemon import spawn_daemon
        from gubernator_tpu.netutil import free_port

        t0 = time.monotonic()
        grpc_addr = f"127.0.0.1:{free_port()}"
        http_addr = f"127.0.0.1:{free_port()}"
        cfg = DaemonConfig(
            grpc_listen_address=grpc_addr, http_listen_address=http_addr,
            cache_size=self.sizes.capacity,
            global_mode="mesh" if self.global_mesh else "",
            behaviors=BehaviorConfig(global_sync_wait_ms=200))
        self.daemon = spawn_daemon(cfg, mesh=self.mesh)
        self.front = Front(grpc_addr, http_addr, self.sizes.clients)
        self.timing["spawn_s"] = round(time.monotonic() - t0, 1)
        inst = self.daemon.instance
        # never fires by itself during the run; phase `sweep` forces
        # the tick through the serving path
        inst.config.sweep_interval_ms = 3_600_000
        h = self.front.get_json("/healthz")
        say(f"pass {self.label}: daemon up in {self.timing['spawn_s']}s, "
            f"/healthz serving={h['serving']}")
        check(h["status"] == "healthy", f"/healthz: {h}")
        sv = h["serving"]
        check(sv["engine"] == self.expect_kind,
              f"engine kind {sv['engine']!r}, expected "
              f"{self.expect_kind!r}")
        d0 = self.mesh.devices.flat[0]
        check(sv["platform"] == d0.platform
              and sv["device_kind"] == d0.device_kind
              and sv["device_count"] == self.mesh.size,
              f"/healthz names the wrong device: {sv}")
        check(sv["native_wire_lane"] is True, "native wire lane absent")

    def close(self):
        self.front.close()
        self.daemon.close()
        self.daemon = self.front = None

    # -- phases ------------------------------------------------------------

    def warm_up(self):
        """Touch every traffic kind once (wall-clock stamps, 1 s
        buckets that expire before the sweep check) so that every
        program the phases run is compiled; then mark the compile
        counters."""
        from gubernator_tpu.types import (Algorithm, Behavior,
                                          RateLimitRequest)
        from gubernator_tpu.wire import req_to_tlv

        t0 = time.monotonic()
        kinds = [dict(), dict(algorithm=Algorithm.LEAKY_BUCKET)]
        if self.global_mesh:
            kinds.append(dict(behavior=Behavior.GLOBAL))
        for n in (1, self.sizes.batch):  # lone request, full batch
            for kw in kinds:
                data = b"".join(req_to_tlv(RateLimitRequest(
                    name=f"warm{self.label}", unique_key=f"w{n}_{i}",
                    hits=1, limit=5, duration=1000, **kw))
                    for i in range(n))
                rs = parse_responses(self.front.get_rate_limits_raw(data))
                check(len(rs) == n and all(r.error == "" for r in rs),
                      f"warm-up {kw} answered with errors")
        self._force_sweep()
        if self.global_mesh:
            self._wait_folded(min_injected=1)
        self.front.get_json("/healthz")
        self.compiles0 = msum(self.front.metrics(),
                              "gubernator_jit_compiles_total")
        self.timing["warm_up_s"] = round(time.monotonic() - t0, 1)
        say(f"pass {self.label}: warm-up done in "
            f"{self.timing['warm_up_s']}s, compiles so far "
            f"{int(self.compiles0)}")

    def load(self, deadline: float):
        """Make the keys resident through the front door, BATCH per
        call, from concurrent clients.  Every response is checked: a
        batch of fresh distinct keys at one stamp answers with n
        byte-identical responses, and the first is compared with the
        oracle's."""
        from gubernator_tpu.oracle import Oracle

        np = self.np
        want = self.sizes.keys[self.label]
        bs = self.sizes.batch
        n_batches = -(-want // bs)
        done = [0] * n_batches
        stop = threading.Event()

        def one(b: int) -> None:
            if stop.is_set():
                return
            if time.monotonic() > deadline:
                stop.set()
                return
            idx = np.arange(b * bs, min((b + 1) * bs, want))
            stamp = self.load_stamp(b * bs)
            out = self.front.get_rate_limits_raw(
                self.tok.batch(self.kid(idx), stamp), lane=b)
            n = len(idx)
            first = out[:len(out) // n]
            if out != first * n:
                rs = parse_responses(out)
                odd = [r for r in rs if r != rs[0]]
                raise AssertionError(
                    f"load batch {b}: {len(odd)} of {n} responses differ "
                    f"from the first, e.g. {odd[:2]} vs {rs[0]}")
            exp = Oracle().check(self.tok.request(
                int(self.kid(idx[0])), stamp), stamp)
            check(same(parse_responses(first)[0], exp),
                  f"load batch {b}: {parse_responses(first)[0]} != {exp}")
            done[b] = n

        t0 = time.monotonic()
        with ThreadPoolExecutor(self.sizes.clients) as ex:
            for f in [ex.submit(one, b) for b in range(n_batches)]:
                f.result()
        # a deadline stop leaves a prefix plus stragglers: keep only
        # the contiguous prefix addressable by the later phases — the
        # rest stay resident and are counted for the sweep check
        self.loaded = sum(done)
        prefix = 0
        for n in done:
            if not n:
                break
            prefix += n
        self.addressable = prefix
        self.timing["load_s"] = round(time.monotonic() - t0, 1)
        if self.loaded < want:
            self.reduced["keys"] = {"wanted": want, "loaded": self.loaded}
        say(f"pass {self.label}: {self.loaded} keys resident in "
            f"{self.timing['load_s']}s")
        check(self.addressable >= bs, "no complete load batch")

    def load_stamp(self, key_index: int) -> int:
        """created_at of the load batch that inserted key index i."""
        return self.t0 + key_index // self.sizes.batch % 1000

    def zipf_stream(self):
        """Seeded Zipf(1.1) stream over the resident keys, one client,
        compared response-for-response with the oracle replayed on the
        host.  Stamps advance 150 ms per batch, so the stream crosses
        the 10 s bucket expiry: hot keys go OVER_LIMIT, then reset."""
        from gubernator_tpu.oracle import Oracle

        np = self.np
        rng = np.random.default_rng(self.seed)
        bs = self.sizes.batch
        n_batches = -(-self.sizes.zipf_requests // bs)
        oracle = Oracle()
        t_stream = self.t0 + 2_000
        compared = over = 0
        t0 = time.monotonic()
        for b in range(n_batches):
            idx = (rng.zipf(1.1, bs) % self.addressable).astype(np.int64)
            stamp = t_stream + b * 150
            out = parse_responses(self.front.get_rate_limits_raw(
                self.tok.batch(self.kid(idx), stamp)))
            check(len(out) == bs, f"zipf batch {b}: {len(out)} answers")
            for i, r in zip(idx.tolist(), out):
                kid = int(self.kid(i))
                key = self.tok.request(kid, 0).key
                if key not in oracle.items:  # replay its load hit first
                    ls = self.load_stamp(i)
                    oracle.check(self.tok.request(kid, ls), ls)
                exp = oracle.check(self.tok.request(kid, stamp), stamp)
                check(same(r, exp),
                      f"zipf batch {b} key {i}: {r} != oracle {exp}")
                over += int(exp.status)
            compared += bs
        self.timing["zipf_s"] = round(time.monotonic() - t0, 1)
        check(0 < over < compared, "stream never crossed the limit")
        say(f"pass {self.label}: Zipf(1.1) stream {compared} responses "
            f"== oracle ({over} OVER_LIMIT) in {self.timing['zipf_s']}s")

    def leaky_slice(self):
        """BASELINE config 2: LEAKY_BUCKET over a small uniform key
        set, every response compared with the oracle."""
        from gubernator_tpu.oracle import Oracle
        from gubernator_tpu.types import Algorithm

        np = self.np
        lk = WireTemplate(np, name=f"leaky{self.label}", hits=1,
                          limit=10, duration=DURATION_MS,
                          algorithm=Algorithm.LEAKY_BUCKET)
        rng = np.random.default_rng(self.seed + 1)
        bs = self.sizes.batch
        oracle = Oracle()
        t_leak = self.t0 + 60_000
        n_batches = -(-self.sizes.leaky_requests // bs)
        over = 0
        for b in range(n_batches):
            idx = rng.integers(0, self.sizes.leaky_keys, bs)
            stamp = t_leak + b * 400
            out = parse_responses(self.front.get_rate_limits_raw(
                lk.batch(self.kid(idx), stamp)))
            for i, r in zip(idx.tolist(), out):
                exp = oracle.check(lk.request(int(self.kid(i)), stamp),
                                   stamp)
                check(same(r, exp),
                      f"leaky batch {b} key {i}: {r} != oracle {exp}")
                over += int(exp.status)
        self.leaky_resident = len(oracle.items)
        check(over > 0, "leaky slice never went over the limit")
        say(f"pass {self.label}: LEAKY_BUCKET slice "
            f"{n_batches * bs} responses == oracle ({over} OVER_LIMIT)")

    def _force_sweep(self):
        """Make the next request's sweep tick due (the tick rides the
        serving path: instance._maybe_sweep after each front-door
        call), then send one."""
        from gubernator_tpu.types import RateLimitRequest
        from gubernator_tpu.wire import req_to_tlv

        eng = self.daemon.instance.engine
        before = eng.sweep_count
        self.daemon.instance._last_sweep = 0
        # stamped in 2001: the tick's own row is expired when the sweep
        # (which follows the request) looks at it
        self.front.get_rate_limits_raw(req_to_tlv(RateLimitRequest(
            name=f"warm{self.label}", unique_key="tick", hits=1, limit=5,
            duration=1000, created_at=1_000_000_000_000)))
        check(eng.sweep_count == before + 1, "sweep tick did not run")
        return eng.live_rows

    def sweep(self):
        """The sweep runs on the populated table (the XLA engine's is
        the Pallas sweep kernel) and its live count equals the keys
        the phases made resident: warm-up rows (1 s, wall clock) have
        expired and are reclaimed, loaded rows expire in the future."""
        time.sleep(1.2)  # past the warm-up/tick rows' 1 s expiry
        eng = self.daemon.instance.engine
        live = self._force_sweep()
        if self.expect_kind == "xla-classic":
            check(eng._pallas_sweep_fn is not None,
                  "the XLA engine's sweep was not the Pallas kernel")
        want = self.loaded + self.leaky_resident
        check(live == want, f"sweep live count {live} != resident {want}")
        say(f"pass {self.label}: sweep live count {live} == keys "
            f"resident ({self.loaded} token + {self.leaky_resident} "
            f"leaky)")

    def _mesh_lane(self) -> dict:
        return self.front.get_json("/debug/audit")["lanes"]["mesh"]

    def _wait_folded(self, min_injected: int, timeout: float = 60.0):
        end = time.monotonic() + timeout
        while True:
            m = self._mesh_lane()
            if m["injected"] >= min_injected \
                    and m["injected"] == m["folded"]:
                return m
            check(time.monotonic() < end,
                  f"mesh lane did not fold: {m}")
            time.sleep(0.1)

    def global_mesh_phase(self):
        """BASELINE config 4 on this pod: Behavior.GLOBAL traffic under
        GUBER_GLOBAL_MODE=mesh.  Responses equal the oracle's, the
        reconcile collective folds, conserves, and never degrades."""
        from gubernator_tpu.oracle import Oracle
        from gubernator_tpu.types import Behavior

        np = self.np
        gl = WireTemplate(np, name=f"global{self.label}", hits=2,
                          limit=1_000_000, duration=600_000,
                          behavior=Behavior.GLOBAL)
        rng = np.random.default_rng(self.seed + 2)
        bs = self.sizes.batch
        oracle = Oracle()
        before = self._mesh_lane()["injected"]
        t_g = self.t0 + 120_000
        n_batches = -(-self.sizes.global_requests // bs)
        for b in range(n_batches):
            idx = rng.integers(0, self.sizes.global_keys, bs)
            stamp = t_g + b * 10
            out = parse_responses(self.front.get_rate_limits_raw(
                gl.batch(self.kid(idx), stamp)))
            for i, r in zip(idx.tolist(), out):
                exp = oracle.check(gl.request(int(self.kid(i)), stamp),
                                   stamp)
                check(same(r, exp),
                      f"GLOBAL batch {b} key {i}: {r} != oracle {exp}")
        sent = n_batches * bs * 2
        m = self._wait_folded(min_injected=before + sent)
        check(m["injected"] - before == sent,
              f"mesh lane injected {m['injected'] - before} hits, "
              f"sent {sent}")
        mt = self.front.metrics()
        folds = mt.get("gubernator_mesh_global_folds_total", 0)
        check(folds > 0, "no mesh-GLOBAL fold ran")
        check(mt.get("gubernator_mesh_global_fold_errors_total", 0) == 0,
              "mesh-GLOBAL fold errors")
        check(mt.get("gubernator_mesh_global_degraded", 0) == 0,
              "mesh-GLOBAL tier degraded")
        check(mt.get("gubernator_mesh_global_keys", 0)
              >= self.sizes.global_keys, "GLOBAL keys not pinned")
        say(f"pass {self.label}: GLOBAL on the {self.mesh.size}-device "
            f"mesh — {sent} hits injected == folded, {int(folds)} folds, "
            f"0 fold errors, not degraded")

    def http_flow(self):
        """The verify skill's recipe over the HTTP gateway."""
        st, rem = [], []
        for _ in range(5):
            r = self.front.post_json("/v1/GetRateLimits", {"requests": [{
                "name": f"api{self.label}", "uniqueKey": "u1", "hits": 1,
                "limit": 3, "duration": 5000}]})["responses"][0]
            st.append(int(r.get("status", 0)))
            rem.append(int(r.get("remaining", 0)))
        check(st == [0, 0, 0, 1, 1] and rem == [2, 1, 0, 0, 0],
              f"HTTP flow: status {st} remaining {rem}")
        check("gubernator_cache_size" in self.front.get_text("/metrics"),
              "/metrics lacks the cache gauge")
        say(f"pass {self.label}: HTTP limit=3 flow status {st} "
            f"remaining {rem}; /healthz, /metrics ok")

    def nothing_swallowed(self):
        """No failure was caught on the way: events, timeouts, stalls,
        the lane that carried the traffic, compiles after warm-up."""
        for kind in ("engine_fallback", "wave_error", "wave_stalled",
                     "wave_timeout", "mesh_degraded", "degraded"):
            ev = self.front.get_json(f"/debug/events?kind={kind}")
            check(not ev["events"], f"{kind} events: {ev['events'][:2]}")
        mt = self.front.metrics()
        check(msum(mt, "gubernator_dispatcher_wave_timeouts_total") == 0
              and msum(mt, "gubernator_dispatcher_stall_events_total") == 0,
              "dispatcher stall or result timeout")
        lanes = {k.split('lane="')[1].split('"')[0]: int(v)
                 for k, v in mt.items()
                 if k.startswith("gubernator_wire_lane_requests_total")}
        native = sum(v for k, v in lanes.items() if "pb2" not in k)
        pb2 = sum(v for k, v in lanes.items() if "pb2" in k)
        check(native >= self.loaded and pb2 <= 0.001 * native,
              f"wire lanes: {lanes}")
        new = msum(mt, "gubernator_jit_compiles_total") - self.compiles0
        check(new == 0, f"{int(new)} compiles after warm-up: " + str(
            {k: v for k, v in mt.items()
             if k.startswith("gubernator_jit_compiles_total")}))
        say(f"pass {self.label}: nothing swallowed — no error/stall/"
            f"timeout/degraded events, wire lanes {lanes}, 0 compiles "
            f"after warm-up")

    def memory(self) -> list:
        rows = []
        for d in self.mesh.devices.flat:
            ms = d.memory_stats() or {}
            rows.append({"id": d.id,
                         "bytes_in_use": ms.get("bytes_in_use"),
                         "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
        return rows


def run_pass(p: Pass, deadline: float) -> dict:
    p.spawn()
    try:
        p.warm_up()
        p.load(deadline)
        p.zipf_stream()
        p.leaky_slice()
        p.sweep()
        if p.global_mesh:
            p.global_mesh_phase()
        p.http_flow()
        p.nothing_swallowed()
        mem = p.memory()
        say(f"pass {p.label}: device memory {mem}")
        in_use = [m["bytes_in_use"] for m in mem]
        if len(in_use) > 1 and None not in in_use:
            check(max(in_use) <= 1.05 * min(in_use),
                  f"table not spread evenly: {in_use}")
    finally:
        p.close()
    return {"engine": p.expect_kind, "timing_s": p.timing,
            "keys_resident": p.loaded, "reduced": p.reduced,
            "memory": mem}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend, kernels "
                         "interpreted, output labelled cpu")
    ap.add_argument("--chips", type=int, default=1,
                    help="devices in the serving mesh (default 1)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sizes = Sizes(args.cpu_rehearsal)

    # ---- set-up that must precede jax -----------------------------------
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
        # the branch a TPU backend takes by default, rehearsed
        os.environ["GUBER_PALLAS_SWEEP"] = "1"
    for k in ("GUBER_ENGINE", "GUBER_STEP_IMPL", "GUBER_GLOBAL_MODE",
              "GUBER_WAVE_BUCKETS"):
        os.environ.pop(k, None)  # the passes choose; nothing inherited
    if sizes.wave_buckets:
        os.environ["GUBER_WAVE_BUCKETS"] = sizes.wave_buckets
    native_s = build_native()
    from gubernator_tpu import compilecache

    cache_dir = compilecache.setup()

    import jax
    import jaxlib
    import numpy as np

    # compile accounting: persistent-cache hits/misses, and where the
    # backend compile seconds went (set-up time, reported apart)
    cache_events = {"hits": 0, "misses": 0}
    compiles: list = []  # (seconds, fun_name)

    def on_event(name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    def on_duration(name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append((round(secs, 1), kw.get("fun_name", "?")))

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    def compile_report(label: str) -> dict:
        rep = {"programs": len(compiles),
               "backend_compile_s": round(sum(c[0] for c in compiles), 1),
               "slowest": sorted(compiles, reverse=True)[:6],
               "cache": dict(cache_events)}
        say(f"pass {label}: compile accounting {rep}")
        compiles.clear()
        return rep

    # ---- device, first ---------------------------------------------------
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        import libtpu
        libtpu_v = getattr(libtpu, "__version__", "?")
    except ImportError:
        libtpu_v = "absent"
    say(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"devices={device['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_v} "
        f"compile_cache={cache_dir} native_build_s={native_s:.1f}")
    if device["platform"] != "tpu" and not args.cpu_rehearsal:
        say("FAIL: no TPU (use --cpu-rehearsal to rehearse on the CPU)")
        return 1
    if args.cpu_rehearsal:
        say("CPU REHEARSAL: tiny sizes, kernels interpreted — proves "
            "the phases, says nothing about the chip")
    check(args.chips <= len(devs),
          f"--chips {args.chips} but JAX shows {len(devs)} devices")

    from gubernator_tpu.parallel import make_mesh

    mesh = make_mesh(n=args.chips)
    on_tpu = device["platform"] == "tpu"
    results = {}

    # ---- pass A: the default engine selection ---------------------------
    if not on_tpu:
        # off-TPU `auto` is the XLA engine; the rehearsal drives the
        # Mosaic kernel engine (interpreted) through its explicit knob
        os.environ["GUBER_STEP_IMPL"] = "pallas"
    results["A"] = run_pass(
        Pass("A", sizes, args.seed, mesh,
             "pallas-fused" if on_tpu else "pallas-kernel",
             global_mesh=True, np=np),
        deadline=T_START + 0.5 * BUDGET_S)
    results["A"]["compile"] = compile_report("A")
    os.environ.pop("GUBER_STEP_IMPL", None)

    # ---- HBM released before the second daemon starts -------------------
    gc.collect()
    held = [(d.memory_stats() or {}).get("bytes_in_use") for d in
            mesh.devices.flat]
    say(f"between passes: bytes_in_use per device {held}")
    table_bytes = sizes.capacity * 64 // args.chips
    check(all(h is None or h < 0.1 * table_bytes for h in held),
          f"pass A's table still held: {held}")

    # ---- pass B: the classic XLA engine ---------------------------------
    os.environ["GUBER_ENGINE"] = "xla"
    results["B"] = run_pass(
        Pass("B", sizes, args.seed, mesh, "xla-classic",
             global_mesh=False, np=np),
        deadline=T_START + 0.9 * BUDGET_S)
    results["B"]["compile"] = compile_report("B")

    say("summary: " + json.dumps({
        "passes": results, "capacity_rows": sizes.capacity,
        "chips_used": args.chips,
        "total_s": round(time.monotonic() - T_START, 1)}))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
