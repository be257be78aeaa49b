"""Generator child process: drives the daemon's gRPC front door from the
client's side and never imports JAX.

The parent (``run.py``) starts ``python benchmark/harness/gen.py``,
writes one JSON spec line to its stdin and then one JSON command per
line; the child answers each with one JSON line on stdout and puts bulk
arrays into ``.npz`` files in the run directory.

Commands
  {"cmd": "connect", "addr": host:port}
  {"cmd": "warm", "stamp": ms[, "alone": true]}
                                        one call of the cell's shape per
                                        caller (or from the first alone)
  {"cmd": "window", "start_at": monotonic s, "seconds": s, "v0": ms,
   "out": path[, "override": {...}]}    the measured window
  {"cmd": "replay", "calls": n, "step_ms": ms, "v_start": ms,
   "out": path}                         single-caller seeded replay
  {"cmd": "quit"}
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark.harness import traffic as tr  # noqa: E402
from benchmark.harness import wire  # noqa: E402

CALL_TIMEOUT_S = 60.0
WARM_TIMEOUT_S = 240.0  # first touches pin keys and may compile
#: a restored population's warm-up keys live above its indices
WARM_BASE = 1 << 32


class Caller:
    """One connection of its own."""

    def __init__(self, addr: str, index: int, seed: int):
        import grpc

        self.index = index
        self.rng = tr.caller_rng(seed, index)
        self.chan = grpc.insecure_channel(
            addr, options=[("grpc.use_local_subchannel_pool", 1)])
        self.call = self.chan.unary_unary(wire.METHOD)

    def close(self) -> None:
        self.chan.close()


class Records:
    """What the window keeps of every call, appended under a lock."""

    def __init__(self):
        self.mu = threading.Lock()
        self.rows: list = []  # (caller, due, send, done, ok, stamp, idx, raw)

    def add(self, *row) -> None:
        with self.mu:
            self.rows.append(row)

    def save(self, path: str) -> dict:
        rows = sorted(self.rows, key=lambda r: r[2])
        n_calls = len(rows)
        cols = {k: [] for k in ("status", "limit", "remaining",
                                "reset_time")}
        answered = np.zeros(n_calls, np.int64)
        resp_errors = 0
        for c, r in enumerate(rows):
            if r[4]:
                d = wire.decode_responses(r[7])
                answered[c] = len(d["status"])
                resp_errors += d["errors"]
                if answered[c] == len(r[6]):
                    for k in cols:
                        cols[k].append(d[k])
                    continue
                answered[c] = -answered[c] - 1  # wrong count: unusable
            for k in cols:
                cols[k].append(np.full(len(r[6]), -1, np.int64))
        cat = (lambda xs, dt: np.concatenate(xs).astype(dt) if xs
               else np.zeros(0, dt))
        np.savez(
            path,
            caller=np.array([r[0] for r in rows], np.int64),
            due=np.array([r[1] for r in rows], np.float64),
            send=np.array([r[2] for r in rows], np.float64),
            done=np.array([r[3] for r in rows], np.float64),
            ok=np.array([r[4] for r in rows], bool),
            stamp=np.array([r[5] for r in rows], np.int64),
            n=np.array([len(r[6]) for r in rows], np.int64),
            answered=answered,
            key_index=cat([r[6] for r in rows], np.int64),
            **{k: cat(v, np.int64) for k, v in cols.items()})
        return {"calls": n_calls, "response_errors": resp_errors}


class Heartbeat:
    """A thread that sleeps 10 ms at a time and notes every time it woke
    more than 50 ms late: tells a stalled process from a slow server."""

    def __init__(self):
        self.gaps: list = []
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            t0 = time.monotonic()
            time.sleep(0.01)
            gap = time.monotonic() - t0
            if gap > 0.05:
                self.gaps.append((t0, gap))

    def stop(self, origin: float) -> list:
        """[(seconds after ``origin``, length of the gap in seconds)]"""
        self._stop.set()
        self._t.join()
        return [(round(t - origin, 3), round(g, 3)) for t, g in self.gaps]


class Generator:
    def __init__(self, spec: dict):
        self.spec = spec
        self.seed = spec["seed"]
        self.traffic = spec["traffic"]
        self.pop = spec["population"]
        self.g = spec["index"]
        per = self.traffic["callers"] // self.traffic["generators"]
        self.caller_ids = list(range(self.g * per, (self.g + 1) * per))
        self.tpl = wire.RequestTemplate(
            name=self.pop["name"], hits=self.pop["hits"],
            limit=self.pop["limit"], duration=self.pop["duration_ms"],
            behavior=self.pop.get("behavior", 0))
        self.callers: list[Caller] = []

    # -- helpers -----------------------------------------------------------

    def _indices(self, rng) -> np.ndarray:
        return tr.sample_indices(rng, self.traffic["keys"],
                                 self.traffic["requests_per_call"],
                                 self.pop["keys"])

    def _bytes(self, idx: np.ndarray, stamp: int) -> bytes:
        return self.tpl.call(tr.key_id(idx, self.seed), stamp)

    # -- commands ----------------------------------------------------------

    def connect(self, addr: str) -> dict:
        self.callers = [Caller(addr, c, self.seed)
                        for c in self.caller_ids]
        return {}

    def warm(self, stamp: int, alone: bool = False) -> dict:
        """One call of the cell's shape per caller, all at once (so
        coalesced wave widths are touched too).  A restored population
        is warmed on throw-away keys beside it, so that its restored
        state stays as made; any other on its OWN keys, at a stamp whose
        buckets have expired when the window opens (a GLOBAL key is
        pinned into the replica on first touch: that belongs to set-up,
        and the replica holds the population, not 32,000 strangers)."""
        n = self.traffic["requests_per_call"]
        bad: list = []

        def one(c: Caller) -> None:
            idx = c.index * n + np.arange(n, dtype=np.int64)
            idx = (WARM_BASE + idx if self.pop.get("restore")
                   else idx % self.pop["keys"])
            try:
                d = wire.decode_responses(
                    c.call(self._bytes(idx, stamp), timeout=WARM_TIMEOUT_S))
                if len(d["status"]) != n or d["errors"]:
                    bad.append(f"caller {c.index}: {len(d['status'])} "
                               f"answers, {d['errors']} errors")
            except Exception as e:  # noqa: BLE001 - reported to the parent
                bad.append(f"caller {c.index}: {e!r}")

        ts = [threading.Thread(target=one, args=(c,))
              for c in (self.callers[:1] if alone else self.callers)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return {"bad": bad}

    def window(self, start_at: float, seconds: float, v0: int,
               out: str, override: dict | None = None) -> dict:
        if override:  # the sweep's rate and arrival process
            self.traffic = {**self.traffic, **override}
        rec = Records()
        beat = Heartbeat()
        if self.traffic["loop"] == "closed":
            self._closed(rec, start_at, seconds, v0)
        else:
            self._open(rec, start_at, seconds, v0)
        return {**rec.save(out), "stalls": beat.stop(start_at)}

    def _closed(self, rec: Records, start_at: float, seconds: float,
                v0: int) -> None:
        end = start_at + seconds

        def loop(c: Caller) -> None:
            time.sleep(max(0.0, start_at - time.monotonic()))
            while True:
                idx = self._indices(c.rng)
                t0 = time.monotonic()
                if t0 >= end:
                    return
                stamp = v0 + int((t0 - start_at) * 1000)
                try:
                    raw = c.call(self._bytes(idx, stamp),
                                 timeout=CALL_TIMEOUT_S)
                    ok = True
                except Exception:  # noqa: BLE001 - counted as failed
                    raw, ok = b"", False
                rec.add(c.index, t0, t0, time.monotonic(), ok, stamp,
                        idx, raw)

        ts = [threading.Thread(target=loop, args=(c,)) for c in self.callers]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    def _open(self, rec: Records, start_at: float, seconds: float,
              v0: int) -> None:
        due_all, conn_all = tr.open_schedule(self.traffic, seconds,
                                             self.seed)
        by_conn = {c.index: c for c in self.callers}
        mine = np.nonzero(np.isin(conn_all, list(by_conn)))[0]
        pending = threading.Semaphore(0)

        def finish(fut, c_index, due, sent, stamp, idx) -> None:
            done = time.monotonic()
            try:
                raw, ok = fut.result(), True
            except Exception:  # noqa: BLE001 - counted as failed
                raw, ok = b"", False
            rec.add(c_index, due, sent, done, ok, stamp, idx, raw)
            pending.release()

        for k in mine:
            c = by_conn[int(conn_all[k])]
            due = start_at + float(due_all[k])
            idx = self._indices(c.rng)
            kids = tr.key_id(idx, self.seed)
            # sleep to just before the due time, then spin: a late
            # generator would be read as a fast server
            while True:
                left = due - time.monotonic()
                if left <= 0:
                    break
                if left > 0.0005:
                    time.sleep(left - 0.0005)
            stamp = v0 + int((due - start_at) * 1000)
            data = self.tpl.call(kids, stamp)
            sent = time.monotonic()
            fut = c.call.future(data, timeout=CALL_TIMEOUT_S)
            fut.add_done_callback(
                lambda f, a=(c.index, due, sent, stamp, idx): finish(f, *a))
        for _ in mine:
            pending.acquire()

    def replay(self, calls: int, step_ms: int, v_start: int,
               out: str) -> dict:
        """One caller, one call after another, stamps ``step_ms`` apart
        (so the stream crosses the bucket's expiry): the answers are
        compared one by one with the reference, in order."""
        rec = Records()
        c = self.callers[0]
        rng = tr.caller_rng(self.seed, 1 << 20)
        for k in range(calls):
            idx = self._indices(rng)
            stamp = v_start + k * step_ms
            t0 = time.monotonic()
            try:
                raw, ok = c.call(self._bytes(idx, stamp),
                                 timeout=CALL_TIMEOUT_S), True
            except Exception:  # noqa: BLE001 - counted as failed
                raw, ok = b"", False
            rec.add(c.index, t0, t0, time.monotonic(), ok, stamp, idx, raw)
        return rec.save(out)

    def close(self) -> None:
        for c in self.callers:
            c.close()


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    if spec.get("cores"):
        os.sched_setaffinity(0, spec["cores"])
    gen = Generator(spec)
    print(json.dumps({"ready": True, "jax_imported": "jax" in sys.modules}),
          flush=True)
    try:
        for line in sys.stdin:
            cmd = json.loads(line)
            name = cmd.pop("cmd")
            if name == "quit":
                break
            out = getattr(gen, name)(**cmd)
            out["jax_imported"] = "jax" in sys.modules
            print(json.dumps(out), flush=True)
    finally:
        gen.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
