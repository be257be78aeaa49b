#!/usr/bin/env python
"""Multithreaded soak of ops/_native.cpp under TSan/ASan.

Loads a SANITIZED build of the extension (``make tsan`` / ``make
asan`` put it under build/<san>/) and hammers every exported primitive
from N concurrent threads over shared and per-thread buffers — the
exact concurrency shape the serving path produces (parse on gRPC
handler threads, pack into pool-leased matrices, response build on
caller threads, TLV stamping on the forward path).

Deliberately imports NOTHING from gubernator_tpu: the package import
pulls in jax, whose runtime under a preloaded sanitizer is pure noise.
Request bytes are built with a 30-line proto encoder instead; numpy is
the only dependency.

Self-re-exec: sanitizer runtimes must be loaded before CPython, so the
script re-launches itself with LD_PRELOAD=<libtsan/libasan> (plus the
suppressions file for TSan and detect_leaks=0 for ASan — CPython's
intentional leaks are not our bugs) unless the runtime is already in.

Exit status is the sanitizer's: a detected race/error fails the run
(`halt_on_error=1`).
"""
from __future__ import annotations

import argparse
import importlib.util
import os
import subprocess
import sys
import sysconfig
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_SAN_LIB = {"tsan": "libtsan.so", "asan": "libasan.so"}


def _find_so(san: str) -> str:
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    path = os.path.join(ROOT, "build", san, "gubernator_tpu", "ops",
                        f"_native{suffix}")
    if not os.path.exists(path):
        raise SystemExit(
            f"no sanitized extension at {path} — run `make {san}` "
            f"(or GUBER_NATIVE_SAN={san} setup_native.py build_ext "
            f"--build-lib build/{san})")
    return path


def _reexec_under(san: str) -> None:
    """Re-launch with the sanitizer runtime preloaded (idempotent)."""
    if os.environ.get("_GUBER_SOAK_PRELOADED") == san:
        return
    lib = subprocess.run(
        ["g++", f"-print-file-name={_SAN_LIB[san]}"],
        capture_output=True, text=True).stdout.strip()
    if not lib or not os.path.exists(lib):
        raise SystemExit(f"cannot locate {_SAN_LIB[san]} (need g++ "
                         f"with sanitizer runtimes)")
    env = dict(os.environ)
    env["_GUBER_SOAK_PRELOADED"] = san
    env["LD_PRELOAD"] = lib
    if san == "tsan":
        supp = os.path.join(HERE, "tsan.supp")
        env["TSAN_OPTIONS"] = (f"suppressions={supp} halt_on_error=1 "
                               f"report_signal_unsafe=0 "
                               f"second_deadlock_stack=1")
    else:
        # CPython leaks interned objects by design; arena-allocator
        # "leaks" would drown real extension bugs
        env["ASAN_OPTIONS"] = ("detect_leaks=0 "
                               "allocator_may_return_null=1")
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def _varint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _field(num: int, v: int) -> bytes:
    return bytes([num << 3]) + _varint(v)


def _req_tlv(name: bytes, key: bytes, hits: int, limit: int,
             duration: int, created: int = 0) -> bytes:
    payload = (b"\x0a" + _varint(len(name)) + name
               + b"\x12" + _varint(len(key)) + key
               + _field(3, hits) + _field(4, limit) + _field(5, duration))
    if created:
        payload += _field(10, created)
    return b"\x0a" + _varint(len(payload)) + payload


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--san", choices=("tsan", "asan"), required=True)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--iters", type=int, default=300)
    args = ap.parse_args()
    _reexec_under(args.san)

    import numpy as np  # after re-exec: numpy loads under the runtime

    spec = importlib.util.spec_from_file_location(
        "gubernator_tpu.ops._native", _find_so(args.san))
    native = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(native)

    DAY = 24 * 3_600_000
    NOW = 1_700_000_000_000
    n_req = 48
    data = b"".join(
        _req_tlv(b"soak", f"k{i}".encode(), hits=2, limit=1000,
                 duration=DAY, created=(NOW + i if i % 3 == 0 else 0))
        for i in range(n_req))
    DURATION_MAX = (1 << 63) - 1
    VALUE_MAX = (1 << 62) - 1
    EFF_MAX = 1 << 31
    TD_BOUND = (1 << 62) - 1
    # the six approximate calendar widths (types.GREGORIAN_APPROX_MS)
    GREG_WIDTHS = np.array([60_000, 3_600_000, DAY, 7 * DAY, 30 * DAY,
                            365 * DAY], "<i8").tobytes()

    errs: list = []
    barrier = threading.Barrier(args.threads)
    # cold-store contract (tiering.py): the table is NOT internally
    # locked — TierController._mu serializes every access.  The soak
    # mirrors that exactly: a shared store behind ONE lock (the
    # sanitizer proves the external-locking discipline suffices) plus
    # an unshared per-thread store hammered lock-free.
    has_cold = hasattr(native, "cold_new")
    shared_cold = native.cold_new(64) if has_cold else None
    shared_cold_mu = threading.Lock()

    def cold_churn(store, base: int, i: int, np) -> None:
        row = np.arange(8, dtype="<i8") + i
        for j in range(16):
            kh = base + ((i * 16 + j) % 97) + 1
            native.cold_put(store, kh, row.tobytes())
            got = native.cold_get(store, kh)
            assert got is not None and len(got) == 64
            if j % 3 == 0:
                native.cold_pop(store, kh)
        keys = np.arange(base + 1, base + 33, dtype="<u8")
        out = np.zeros(32, np.uint8)
        native.cold_contains(store, keys.tobytes(), out)
        # a wave's cold lane in one pass (ISSUE 42): find-or-insert,
        # the transition, the answers patched in place — 48 rows over 24
        # keys (each twice, stamps out of index order, some unset), token
        # and leaky, on a table that has to grow for them now and then
        m = 48
        kh = ((np.arange(m, dtype="<u8") % 24) + base + 200 + (i % 5) * 24)
        alg = (np.arange(m) % 3 == 0).astype("<i8")
        lim = np.full(m, 100, "<i8")
        dur = np.full(m, 60_000, "<i8")
        stamp = NOW + i * 1000 - np.arange(m, dtype="<i8") * (i % 2)
        stamp[::7] = 0
        st = np.full(m, -1, np.int32)
        o_lim, o_rem, o_rst = (np.zeros(m, "<i8") for _ in range(3))
        full = np.ones(m, bool)
        served, created, keys = native.cold_apply_batch(
            store, kh, np.arange(m, dtype="<i8"),
            np.ones(m, "<i8"), lim, dur, dur, np.zeros(m, "<i8"),
            np.zeros(m, "<i8"), alg, lim * 2, stamp, NOW + i * 1000,
            TD_BOUND, 1 << 31, st, o_lim, o_rem, o_rst, full)
        assert served == m and created <= 24 and len(keys) == 8 * 24
        assert not full.any() and (st >= 0).all() and (o_lim == 100).all()
        n, kb, rb = native.cold_snapshot(store)
        assert len(kb) == 8 * n and len(rb) == 64 * n
        assert native.cold_len(store) == n

    def worker(t: int) -> None:
        try:
            m = 64
            a64 = np.zeros((8, m), np.int64)
            a32 = np.zeros((3, m), np.int32)
            own_cold = native.cold_new(16) if has_cold else None
            barrier.wait(timeout=60)
            for i in range(args.iters):
                # parse: read-only over the SHARED request bytes
                parsed = native.parse_get_rate_limits(data)
                assert parsed is not None and parsed[0] == n_req
                toff = np.frombuffer(parsed[9], "<u8").astype(np.int64)
                tlen = np.frombuffer(parsed[10], "<u8").astype(np.int64)
                created = np.frombuffer(parsed[11], "<i8")
                # stamp: shared bytes in, fresh bytes out
                fwd = native.stamp_req_tlvs(
                    data, toff, tlen,
                    np.ascontiguousarray(created), NOW + i)
                assert native.count_req_items(fwd) == n_req
                # fused pack into THIS thread's leased matrices
                res = native.pack_wire_wave(
                    fwd, NOW + i, a64, a32, m, DURATION_MAX, VALUE_MAX,
                    EFF_MAX, TD_BOUND, 0, 0, GREG_WIDTHS)
                assert res is not None and res[0] == n_req
                # response build out of shared-shape columns
                st = np.zeros(n_req, np.int32)
                lim = np.full(n_req, 1000, np.int64)
                rem = np.full(n_req, 998, np.int64)
                rst = np.full(n_req, NOW + DAY, np.int64)
                out = native.build_rate_limit_resps(st, lim, rem, rst,
                                                    None)
                sp = native.split_resp_items(out)
                assert sp is not None and sp[0] == n_req
                # hashing over shared string lists
                buf, n = native.fnv1a64_pair_batch(
                    ["soak"] * 8, [f"k{j}" for j in range(8)])
                assert n == 8
                # cold-store churn: per-thread store lock-free, the
                # shared store under the tier's external-lock contract
                if has_cold:
                    cold_churn(own_cold, t * 1_000_000, i, np)
                    with shared_cold_mu:
                        cold_churn(shared_cold, 77_000_000, i, np)
        except Exception as e:  # noqa: BLE001 - reported below
            errs.append(f"thread {t}: {e!r}")

    threads = [threading.Thread(target=worker, args=(t,),
                                name=f"native-soak-{t}")
               for t in range(args.threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
    if any(th.is_alive() for th in threads):
        print("FAIL: soak threads stuck", file=sys.stderr)
        return 1
    if errs:
        print("FAIL:", *errs[:5], sep="\n  ", file=sys.stderr)
        return 1
    print(f"native soak clean under {args.san}: {args.threads} threads "
          f"x {args.iters} iters")
    return 0


if __name__ == "__main__":
    sys.exit(main())
