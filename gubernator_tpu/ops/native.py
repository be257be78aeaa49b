"""Python face of the native extension (raises ImportError if unbuilt).

hashing.py imports this lazily and falls back to pure numpy; both
return RAW FNV-1a 64 values — the avalanche finalizer is applied by
hashing.mix64_np either way.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from ..types import (DURATION_MAX, EFF_MAX, GREGORIAN_APPROX_MS, TD_BOUND,
                     VALUE_MAX, GregorianDuration)
from . import _native  # ImportError here means: run `make native`

#: the entry point the newest ``_native.cpp`` added (PR 46; PR 42's
#: was ``cold_apply_batch``, PR 41's ``cold_put_batch``, PR 40's ``gregorian_end``, PR 37's
#: ``thread_files``, PR 36's ``route_plan`` / ``route_fill``): a build
#: without it is older than the source
NEWEST = "cold_take_batch"

if not hasattr(_native, NEWEST):
    # NOT an ImportError: every importer reads that as "no extension"
    # and falls back to numpy — a build older than _native.cpp is a
    # broken checkout, not an optional feature switched off
    raise RuntimeError(
        f"{_native.__file__} is older than _native.cpp (no {NEWEST}): "
        "rebuild it with `make native`")

#: types.GREGORIAN_APPROX_MS by ordinal, as the i64le[6] the C++ pass
#: reads a calendar row's ``eff_ms`` from
_GREG_WIDTHS = np.array([GREGORIAN_APPROX_MS[d] for d in GregorianDuration],
                        "<i8").tobytes()

def hash_keys(keys: Sequence[str]) -> np.ndarray:
    """Raw FNV-1a64 of each key string → uint64[n]."""
    buf, n = _native.fnv1a64_batch(keys)
    return np.frombuffer(buf, dtype="<u8", count=n).copy()


def hash_pairs(names: Sequence[str], unique_keys: Sequence[str]) -> np.ndarray:
    """Raw FNV-1a64 of name + "_" + unique_key without string joins."""
    buf, n = _native.fnv1a64_pair_batch(names, unique_keys)
    return np.frombuffer(buf, dtype="<u8", count=n).copy()


def parse_get_rate_limits(data: bytes):
    """GetRateLimitsReq wire bytes → packed column dict, or None when the
    message needs the pb2 fallback (metadata, empty name/key, unknown
    fields).  ``khash_raw`` is RAW FNV-1a64 — apply hashing.mix64_np."""
    r = _native.parse_get_rate_limits(data)
    if r is None:
        return None
    (n, kh, hits, limit, dur, alg, beh, burst, beh_or, toff, tlen,
     created, nh) = r
    return {
        "n": n,
        "khash_raw": np.frombuffer(kh, "<u8", count=n),
        "hits": np.frombuffer(hits, "<i8", count=n),
        "limit": np.frombuffer(limit, "<i8", count=n),
        "duration": np.frombuffer(dur, "<i8", count=n),
        "algorithm": np.frombuffer(alg, "<i4", count=n),
        "behavior": np.frombuffer(beh, "<i4", count=n),
        "burst": np.frombuffer(burst, "<i8", count=n),
        "behavior_or": int(beh_or),
        # per-request TLV ranges in the input bytes: a clustered daemon
        # forwards owner sub-batches by slicing these verbatim (peer
        # wire framing is byte-compatible, field 1 on both messages)
        "tlv_off": np.frombuffer(toff, "<u8", count=n),
        "tlv_len": np.frombuffer(tlen, "<u8", count=n),
        # caller's accepted-at clock (field 10, 0 = unset): forwarded
        # rows apply at THIS time base, not the owner's wall clock
        "created_at": np.frombuffer(created, "<i8", count=n),
        # raw FNV-1a64 of each request's `name` alone (the state
        # khash_raw continues from): the analytics tap's tenant learn
        "name_hash": np.frombuffer(nh, "<u8", count=n),
    }


def stamp_req_tlvs(data: bytes, tlv_off: np.ndarray, tlv_len: np.ndarray,
                   created_at: np.ndarray, stamp_ms: int) -> bytes:
    """Join the given request TLV slices of ``data``, appending
    ``created_at = stamp_ms`` (field 10) to every slice that doesn't
    already carry a caller stamp (created_at[i] == 0).  The forward
    hop's bulk caller-clock stamp — see wire.tlv_with_created for the
    one-slice codec-free twin and types.RateLimitRequest.created_at
    for why the stamp exists."""
    return _native.stamp_req_tlvs(
        data,
        np.ascontiguousarray(tlv_off, "<i8"),
        np.ascontiguousarray(tlv_len, "<i8"),
        np.ascontiguousarray(created_at, "<i8"),
        int(stamp_ms))


def count_req_items(data: bytes, excluded: int = 0):
    """TLV count of a GetRateLimitsReq / GetPeerRateLimitsReq — the
    fused ingest's pre-pass, which sizes the call's pair before the
    single full parse — or None on framing the fast lane doesn't model.
    ``excluded``: Behavior bits the caller's lane does not serve; None
    at the FIRST request that carries one, before anything is
    allocated.  0 (the default) reads no request's payload."""
    return _native.count_req_items(data, int(excluded))


def pack_wire_wave(data: bytes, now_ms: int, a64: np.ndarray,
                   a32: np.ndarray, value_domain=None):
    """Fused wire ingest: parse + validate + clamp + key-hash (FNV-1a64
    → mix64, zero-remapped) one request message and write the rows
    straight into the call's pair in the upload layout (``a64`` [8, m]
    i64, ``a32`` [3, m] i32 — core/batch.py › PACK64/PACK32; every cell
    is written, rows past n as padding, so ``np.empty`` will do).

    A DURATION_IS_GREGORIAN row takes its ``greg_end`` — the end of the
    calendar period that holds the clock the row is applied at
    (gregorian.py, the rule) — and its approximate width from the same
    pass.  Returns None (caller falls back to the classic numpy pack)
    for anything the lane doesn't model: pb2 framing, n > m, or a
    calendar row only the classic lane answers (an ordinal outside
    0..5, a clock ``gregorian_end`` does not take).  Otherwise (n,
    khash u64[n] MIXED, behavior_or, tlv_off, tlv_len, name_hash u64[n]
    — raw FNV-1a64 of each request's name alone —, derived), where
    derived = (ood, leaky, greg, now_lo, now_hi, monotone) is what
    ``ShardedEngine.lay_out`` derives of a call's rows, from the same
    pass: ``value_domain`` is the engine's (VALUE_BOUND, EFF_BOUND),
    None for the full domain.  Clamp bounds and the six approximate
    widths are passed from types.py so the constants have one home;
    the arithmetic is pinned bit-identical to core/batch.py ›
    pack_columns by tests/test_native.py and
    tests/test_native_calendar.py, the derived values to ``lay_out`` by
    tests/test_wave_layout.py."""
    m = a64.shape[1]
    if not (a64.flags.c_contiguous and a32.flags.c_contiguous
            and a64.dtype == np.int64 and a32.dtype == np.int32
            and a64.shape == (8, m) and a32.shape == (3, m)):
        raise ValueError("pack_wire_wave wants C-contiguous [8, m] i64 "
                         "and [3, m] i32")
    vb, eb = value_domain if value_domain is not None else (0, 0)
    r = _native.pack_wire_wave(data, int(now_ms), a64, a32, m,
                               DURATION_MAX, VALUE_MAX, EFF_MAX,
                               TD_BOUND, vb, eb, _GREG_WIDTHS)
    if r is None:
        return None
    n, kh, beh_or, toff, tlen, nh, derived = r
    return (n,
            np.frombuffer(kh, "<u8", count=n),
            int(beh_or),
            np.frombuffer(toff, "<u8", count=n),
            np.frombuffer(tlen, "<u8", count=n),
            np.frombuffer(nh, "<u8", count=n),
            _derived(derived))


def gregorian_end(now_ms: int, ordinal: int):
    """``gregorian.gregorian_expiration`` as the fused ingest computes
    it: the end (epoch-ms) of the calendar period that holds
    ``now_ms``, or None for an ordinal outside 0..5 or a clock outside
    [0001-01-01, 9999-01-01) — what ``pack_wire_wave`` declines a call
    for.  tests/test_native_calendar.py holds the two to each other."""
    return _native.gregorian_end(int(now_ms), int(ordinal))


def _derived(d):
    ood, leaky, greg, now_lo, now_hi, monotone = d
    return (np.frombuffer(ood, "<i8") if ood else None, leaky, greg,
            now_lo, now_hi, monotone)


def derive_rows(m64: np.ndarray, m32: np.ndarray, mslot=None,
                value_domain=None):
    """What ``pack_wire_wave`` derives of a call's rows, for rows laid
    out in Python (``m64`` [8, n] i64, ``m32`` [3, n] i32, rows
    contiguous): (ood, leaky, greg, now_lo, now_hi, monotone) in ONE pass
    that keeps the GIL — a handler's ~25 numpy calls, each to be won
    back from the other handlers, become one.  ``mslot``: i32[n], rows
    >= 0 exempt from the domain; ``value_domain``: as above."""
    vb, eb = value_domain if value_domain is not None else (0, 0)
    if mslot is not None:
        mslot = np.ascontiguousarray(mslot, np.int32)
    return _derived(_native.derive_rows(m64, m32, mslot, vb, eb))


def route_plan(khash: np.ndarray, pending, shards: int, buckets):
    """``ShardedEngine._build_waves`` in one pass that keeps the GIL:
    the device waves of rows ``pending`` (i64[k]; None = every row of
    ``khash`` in row order) as [(idx, slots, bw_w, wcnt)] — row indices
    and block slots (read-only i64), the wave's bucket and the rows of
    its densest shard.  ``buckets``: ascending."""
    return [(np.frombuffer(idx, "<i8"), np.frombuffer(slots, "<i8"), bw_w,
             wcnt)
            for idx, slots, bw_w, wcnt in _native.route_plan(
                khash, pending, shards, buckets)]


def route_fill(m64: np.ndarray, m32: np.ndarray, valid, mslot,
               idx: np.ndarray, slots: np.ndarray, a64: np.ndarray,
               a32: np.ndarray, mblk=None) -> None:
    """``ShardedEngine._fill`` in one pass that keeps the GIL: rows
    ``idx`` of the joined matrices (``m64`` [8, N] i64, ``m32`` [3, N]
    i32) at ``slots`` (strictly ascending, as ``route_plan`` lists
    them) of the upload pair (``a64`` [8, m], ``a32`` [3, m]), ``valid``
    (bool[N]) in place of the rows' own where given, padding in every
    other slot, and ``mslot``'s lane (i32[N]) into ``mblk`` (i32[m], -1
    outside the rows; given exactly when ``mslot`` is).  EVERY cell of
    the pair and of ``mblk`` is written — lease the pair with
    ``rows=m``; a wrong argument raises before any is."""
    if mslot is not None:
        mslot = np.ascontiguousarray(mslot, np.int32)
    _native.route_fill(m64, m32, valid, mslot, idx, slots, a64, a32, mblk)


def thread_files(task_dir: str, name: str):
    """``[(tid, bytes of <task_dir>/<tid>/<name>)]`` for every thread
    that has the file, ``None`` where the directory cannot be listed —
    the whole walk with the GIL released once (the thread ledger,
    ``tracing.py › ThreadLedger``)."""
    return _native.thread_files(task_dir, name)


def split_resp_items(data: bytes):
    """RateLimitResp-list wire bytes → (tlv_off, tlv_len, status) per
    item, or None on malformed input (caller falls back to pb2).  Works
    for GetRateLimitsResp and GetPeerRateLimitsResp alike (both carry
    the repeated submessage on field 1)."""
    r = _native.split_resp_items(data)
    if r is None:
        return None
    n, toff, tlen, st = r
    return (np.frombuffer(toff, "<u8", count=n),
            np.frombuffer(tlen, "<u8", count=n),
            np.frombuffer(st, "<i4", count=n))


def build_rate_limit_resps(status: np.ndarray, limit: np.ndarray,
                           remaining: np.ndarray, reset_time: np.ndarray,
                           errors=None) -> bytes:
    """Packed response columns → GetRateLimitsResp wire bytes.
    ``errors``: optional sequence of str/None per response."""
    return _native.build_rate_limit_resps(
        np.ascontiguousarray(status, "<i4"),
        np.ascontiguousarray(limit, "<i8"),
        np.ascontiguousarray(remaining, "<i8"),
        np.ascontiguousarray(reset_time, "<i8"),
        errors if errors is not None else None)


def build_responses_from_columns(result_cols, row_lo: int, row_hi: int,
                                 errors=None) -> bytes:
    """Rows [row_lo, row_hi) of a wave's SHARED result columns →
    GetRateLimitsResp wire bytes, with zero per-request Python objects
    and zero intermediate slices — the caller-thread response-build
    lane of the overlapped wave pipeline (dispatcher.ResultView).

    ``result_cols`` is the dispatcher/engine 5-tuple (status i32,
    limit i64, remaining i64, reset i64, table_full bool); the bool
    column is ignored here (the caller folds it into ``errors``).
    ``errors``: optional sequence of str/None indexed relative to
    ``row_lo``."""
    st, lim, rem, rst = result_cols[:4]
    return _native.build_responses_from_columns(
        np.ascontiguousarray(st, "<i4"),
        np.ascontiguousarray(lim, "<i8"),
        np.ascontiguousarray(rem, "<i8"),
        np.ascontiguousarray(rst, "<i8"),
        int(row_lo), int(row_hi),
        errors if errors is not None else None)
