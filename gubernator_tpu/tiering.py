"""Tiered key store (ISSUE 10): host cold tier behind the device table.

Device-table capacity was the last hard cap on key cardinality: every
engine pins its table at construction and a probe-window-exhausted
insert was an error row ("rate limit table full").  This module turns
that condition into a *tier boundary* instead: a host-memory cold tier
(raw-hash → packed bucket-state rows, store.py-interoperable) sits
behind every device hot tier, and a sketch-rank admission controller
migrates rows between them —

- a request whose key misses the device table (cold-resident, or
  brand-new with the table full) is served EXACTLY from the cold tier
  on the resolve path: ``_host_apply`` mirrors the device transition
  (core/step.py › _apply_position) in plain integer arithmetic, bit
  for bit over the packed input domain, so decisions are byte-identical
  to an uncapped single-tier run.  Which lane applies it is what the
  store IS, not an option: over the native store a wave's cold rows are
  ONE C++ pass (ops/_native.cpp › ``cold_apply_batch``: the order, the
  find-or-insert, ``_host_apply`` statement for statement in 128-bit
  intermediates, the answers patched in place); over the dict store —
  or a built extension without that entry point — the Python loop of
  ``_host_apply`` calls, which is also the reference the pass is held
  to (tests/test_cold_apply_batch.py);
- when a cold key's heavy-hitter rank (analytics.py sketch: the hits
  it is KNOWN to have drawn, ``count - err``) clears the admission
  threshold its row migrates to HBM, evicting the coldest resident row
  of its probe window back to host under a conservation-exact,
  created_at-preserving handoff (all eight value columns move verbatim,
  both directions).  A wave's admitted keys move in ONE migration pass
  (``TierController.migrate``): their device buckets fetched once,
  promotees placed and victims taken out on that image, the image
  written back once, the cold store's side of it one batch call each
  way; a pass moves at most ``MIGRATE_MAX`` keys, and what is over
  stays cold — still answered exactly there — until it is next served.

Coherence: every membership change (serve, create, promote, demote)
happens inside the engine's ``check_packed`` resolve or under the
instance engine lock, so a key is resident in exactly ONE tier at any
decision point.  ``ShardedEngine.check_packed`` pre-masks cold-resident
rows out of the device wave (a cold key hitting a non-full device table
would otherwise insert fresh — a state fork) and serves them here on
the way out.  The pipelined launch/sync lane re-dispatches a wave's
cold rows at sync time together with its erred rows, ONCE
(``ShardedEngine.sync_packed``): one premask, one launch — the erred
rows' retry —, one ``resolve``.

The cold store itself is the native open-addressed table in
ops/_native.cpp (``cold_*`` primitives, khash u64 → 8×i64 row) when the
built extension exports it; a plain dict fallback keeps every semantic
otherwise (GUBER_TIER_NATIVE=0 forces the fallback).
"""
from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, Dict, Optional

import numpy as np

from .tracing import phase
from .types import FRAC_SAFE, TD_BOUND, Algorithm, Behavior

log = logging.getLogger("gubernator_tpu.tiering")

#: cold-row column order — store.py's snapshot layout minus the key
#: column, so snapshot/restore streams cold rows through the exact
#: Loader item codec the device tier already uses.
ROW_COLS = ("meta", "limit", "duration", "eff_ms", "burst", "remaining",
            "t_ms", "expire_at")

_LEAKY = int(Algorithm.LEAKY_BUCKET)
_GREG = int(Behavior.DURATION_IS_GREGORIAN)
_RESET = int(Behavior.RESET_REMAINING)
_DRAIN = int(Behavior.DRAIN_OVER_LIMIT)

#: the all-zero item a missing key adopts — identical to the device's
#: out-of-range gather fill (core/step.py › grow: zeros, eff_ms 1)
_ZERO_ROW = (0, 0, 0, 1, 0, 0, 0, 0)

#: the most keys ONE migration pass promotes (and so the most it
#: demotes): the bound on what a waking tenant costs the wave that
#: admits it.  The pass's device side is one fetch and one write of its
#: keys' distinct buckets (8 KiB each on the bucket engine, padded to
#: pallas_engine.ROW_OP_SIZES, whose largest this is), its host side
#: numpy over [keys, probe window]; what is over the bound stays cold,
#: is answered exactly there, and is admitted when next served
#: (``gubernator_tier_admissions_deferred``).  Chosen on the chip:
#: PERF.md §6 (PR 46).
MIGRATE_MAX = 256

#: flight-recorder events a pass records of each kind (promote, demote):
#: a waking tenant must not flush the ring of everything else
_EVENTS_A_PASS = 4

#: the nine request columns of a wave that a cold row is applied from,
#: in ``_host_apply``'s argument order
_REQ_COLS = ("hits", "limit", "duration", "eff_ms", "greg_end", "behavior",
             "algorithm", "burst", "now")


def _host_apply(row, hits, limit, duration, eff, greg_end, behavior,
                alg, burst, req_now):
    """One request applied to one cold row — the exact host mirror of
    the device transition (core/step.py › _apply_position), in plain
    Python integers over the same packed-clamped input domain
    (core/batch.py › pack_columns keeps every td product ≤ TD_BOUND, so
    no intermediate here can exceed int64 where the device's can't).

    ``row`` is an 8-tuple in ROW_COLS order (None = missing key).
    Returns (status, out_remaining, reset_time, out_limit, new_row).
    """
    if row is None:
        row = _ZERO_ROW
    meta, i_limit, i_duration, i_eff, i_burst, i_rem, i_t, i_exp = row
    i_alg = meta & 1
    i_status = (meta >> 1) & 1

    now = req_now if req_now > i_t else i_t
    is_leaky = alg == _LEAKY
    is_greg = (behavior & _GREG) != 0

    # --- fresh determination (missing/expired/algorithm switch)
    fresh = (now >= i_exp) or (i_alg != alg)
    tok_dur_change = (not is_leaky) and (not fresh) and (duration != i_duration)
    exp1 = i_exp
    if tok_dur_change:
        exp1 = greg_end if is_greg else i_t + eff
        if exp1 <= now:
            fresh = True

    # --- adopt fresh or existing state
    eff_l = eff if is_leaky else 1
    if fresh:
        limit0 = limit
        eff0 = eff
        rem0 = (burst if is_leaky else limit) * eff_l
        t0 = now
        exp0 = now + eff if is_leaky else (greg_end if is_greg else now + eff)
        status0 = 0
    else:
        limit0 = i_limit
        eff0 = i_eff
        rem0 = i_rem
        t0 = i_t
        exp0 = exp1
        status0 = i_status

    # --- leaky denominator change → rescale td fixed point
    if is_leaky and (not fresh) and eff != eff0:
        d = eff0 if eff0 > 1 else 1
        whole = rem0 // d
        frac = rem0 % d
        cap_whole = TD_BOUND // (eff if eff > 1 else 1)
        if whole > cap_whole:
            whole = cap_whole
        frac_ok = eff0 <= FRAC_SAFE and eff <= FRAC_SAFE
        rem0 = whole * eff + ((frac if frac_ok else 0) * eff) // d
    if is_leaky or tok_dur_change:
        eff0 = eff

    # --- RESET_REMAINING (existing items only)
    reset_live = (behavior & _RESET) != 0 and not fresh
    if reset_live:
        rem0 = limit * eff_l
        status0 = 0
    limit_after_reset = limit if (reset_live and not is_leaky) else limit0

    # --- token limit change in place
    if (not is_leaky) and limit != limit_after_reset:
        rem0 = rem0 + limit - limit_after_reset
        if rem0 < 0:
            rem0 = 0
        elif rem0 > limit:
            rem0 = limit
    limit1 = limit

    # --- leaky replenish (exact: elapsed × limit td, clamped to burst)
    burst1 = burst if is_leaky else limit1
    if is_leaky:
        elapsed = now - t0
        cap_td = burst1 * eff0
        safe_el = TD_BOUND // (limit1 if limit1 > 1 else 1)
        if elapsed > safe_el:
            rem0 = cap_td
        else:
            rem0 = rem0 + elapsed * limit1
            if rem0 > cap_td:
                rem0 = cap_td
        t1 = now
    else:
        t1 = t0

    d0 = eff0 if eff0 > 1 else 1
    rate = eff0 // (limit1 if limit1 > 1 else 1) if limit1 > 0 else eff0
    exp_out = now + eff0 if is_leaky else exp0
    # leaky: from the request's OWN stamp, not the clamped clock (the
    # older request — oracle.py, "Leaky fixed point")
    reset_time = req_now + rate if is_leaky else exp_out

    # --- hits
    cost = hits * (eff0 if is_leaky else 1)
    if hits == 0:  # query
        rem2, status1 = rem0, status0
    elif cost <= rem0:
        rem2, status1 = rem0 - cost, 0
    else:
        rem2 = 0 if (behavior & _DRAIN) != 0 else rem0
        status1 = 1

    out_rem = rem2 // d0 if is_leaky else rem2
    new_row = (alg | (status1 << 1), limit1, duration, eff0, burst1,
               rem2, t1, exp_out)
    return status1, out_rem, reset_time, limit1, new_row


class _DictColdStore:
    """Pure-Python cold store: khash → 8-tuple row.  The semantic
    reference for the native table, and the fallback when the built
    extension predates the ``cold_*`` exports (GUBER_TIER_NATIVE=0
    forces it).  NOT thread-safe — TierController._mu serializes."""

    native = False
    #: no batch pass: ``TierController.resolve`` walks the rows itself
    apply_batch = None

    def __init__(self):
        self._d: Dict[int, tuple] = {}

    def __len__(self) -> int:
        return len(self._d)

    def get(self, kh: int):
        return self._d.get(kh)

    def put(self, kh: int, row) -> None:
        self._d[kh] = tuple(row)

    def put_batch(self, keys: np.ndarray, rows: np.ndarray) -> None:
        """``put`` for every (keys[i], rows[i]) — u64[n], i64[n, 8]."""
        self._d.update(zip(keys.tolist(), map(tuple, rows.tolist())))

    def pop(self, kh: int):
        return self._d.pop(kh, None)

    def take_batch(self, keys: np.ndarray, remove: bool = False) -> tuple:
        """(found bool[n], rows i64[n, 8], zeros where not found) of
        ``keys``; ``remove``: each key found is taken out (a key that
        comes twice is found once)."""
        take = self._d.pop if remove else self._d.get
        found = np.zeros(len(keys), bool)
        rows = np.zeros((len(keys), len(ROW_COLS)), np.int64)
        for i, kh in enumerate(keys.tolist()):
            row = take(kh, None)
            if row is not None:
                found[i] = True
                rows[i] = row
        return found, rows

    def contains_batch(self, khash: np.ndarray) -> np.ndarray:
        d = self._d
        return np.fromiter((int(k) in d for k in khash), bool,
                           count=len(khash))

    def snapshot(self):
        """(keys u64[n], rows i64[n, 8]) in arbitrary order."""
        n = len(self._d)
        keys = np.fromiter(self._d.keys(), np.uint64, count=n)
        rows = np.empty((n, len(ROW_COLS)), np.int64)
        for i, r in enumerate(self._d.values()):
            rows[i] = r
        return keys, rows


class _NativeColdStore:
    """ops/_native.cpp ``cold_*`` open-addressed table behind the same
    interface (khash u64 → packed 8×i64 row, linear probing, tombstone
    deletes, load-factor growth in C).  NOT thread-safe —
    TierController._mu serializes."""

    native = True

    def __init__(self, native_mod):
        self._m = native_mod
        self._h = native_mod.cold_new(1024)
        #: a wave's cold lane as ONE C++ pass, where the build has it
        self.apply_batch = (self._apply_batch
                            if hasattr(native_mod, "cold_apply_batch")
                            else None)

    def __len__(self) -> int:
        return self._m.cold_len(self._h)

    def get(self, kh: int):
        b = self._m.cold_get(self._h, kh)
        if b is None:
            return None
        return tuple(int(v) for v in np.frombuffer(b, "<i8", count=8))

    def put(self, kh: int, row) -> None:
        self._m.cold_put(self._h,
                         int(kh),
                         np.asarray(row, "<i8").tobytes())

    def put_batch(self, keys: np.ndarray, rows: np.ndarray) -> None:
        """``put`` for every (keys[i], rows[i]) — u64[n], i64[n, 8] —
        as one C++ pass that grows the table once."""
        self._m.cold_put_batch(self._h,
                               np.ascontiguousarray(keys, "<u8"),
                               np.ascontiguousarray(rows, "<i8"))

    def _apply_batch(self, khash, idxs, req_cols, now_ms: int, cols):
        """Rows ``idxs`` of a wave applied to their keys' rows in
        (effective stamp, index) order and answered into ``cols`` — the
        engine's five response columns, patched in place: status i32,
        limit / remaining / reset i64, full bool, contiguous — as
        ``resolve``'s loop does.  Returns (served, keys created, the
        distinct served keys u64[k] in order of first service);
        OverflowError where the loop raises it."""
        served, created, keys = self._m.cold_apply_batch(
            self._h, np.ascontiguousarray(khash, "<u8"),
            np.ascontiguousarray(idxs, "<i8"),
            *(np.ascontiguousarray(c, "<i8") for c in req_cols),
            int(now_ms), TD_BOUND, FRAC_SAFE, *cols)
        return served, created, np.frombuffer(keys, "<u8")

    def take_batch(self, keys: np.ndarray, remove: bool = False) -> tuple:
        """``_DictColdStore.take_batch`` as ONE C++ pass
        (``cold_take_batch``; a built extension without it: a key at a
        time)."""
        n = len(keys)
        found = np.zeros(n, np.uint8)
        rows = np.zeros((n, len(ROW_COLS)), "<i8")
        if hasattr(self._m, "cold_take_batch"):
            self._m.cold_take_batch(self._h,
                                    np.ascontiguousarray(keys, "<u8"),
                                    rows, found, bool(remove))
        else:
            take = self.pop if remove else self.get
            for i, kh in enumerate(keys.tolist()):
                row = take(kh)
                if row is not None:
                    found[i] = 1
                    rows[i] = row
        return found != 0, rows

    def pop(self, kh: int):
        b = self._m.cold_pop(self._h, kh)
        if b is None:
            return None
        return tuple(int(v) for v in np.frombuffer(b, "<i8", count=8))

    def contains_batch(self, khash: np.ndarray) -> np.ndarray:
        out = np.zeros(len(khash), np.uint8)
        self._m.cold_contains(
            self._h, np.ascontiguousarray(khash, "<u8").tobytes(), out)
        return out != 0

    def snapshot(self):
        n, keys_b, rows_b = self._m.cold_snapshot(self._h)
        keys = np.frombuffer(keys_b, "<u8", count=n).copy()
        rows = np.frombuffer(rows_b, "<i8",
                             count=n * len(ROW_COLS)).reshape(
                                 n, len(ROW_COLS)).copy()
        return keys, rows


def _make_store():
    """Native cold store when the built extension exports the cold_*
    primitives and GUBER_TIER_NATIVE != 0; dict fallback otherwise."""
    if os.environ.get("GUBER_TIER_NATIVE", "1") != "0":
        try:
            from .ops import _native
        except ImportError:
            _native = None
        if _native is not None and hasattr(_native, "cold_new"):
            return _NativeColdStore(_native)
    return _DictColdStore()


class TierController:
    """The admission/demotion controller and the cold tier's single
    front door.  One instance per engine; ``engine.tier`` points here.

    Locking: all tier *membership* changes happen inside the engine's
    ``check_packed`` resolve or under the instance engine lock, which
    serializes them against each other; ``self._mu`` (leaf rank — see
    CONCURRENCY.md) additionally protects the store against concurrent
    READERS off the serving path (stats, snapshot, seeding probes).
    Never call an engine/device method while holding ``self._mu``.
    """

    def __init__(self, engine, rank_fn: Optional[Callable[[int], int]] = None,
                 promote_threshold: int = 8, metrics=None, recorder=None,
                 fault: Optional[Callable[[str], None]] = None,
                 skip_victim: Optional[Callable[[int], bool]] = None,
                 tap: Optional[Callable] = None,
                 rank_batch: Optional[Callable] = None):
        self._mu = threading.Lock()
        self._store = _make_store()  # guarded-by: self._mu
        self.rank_fn = rank_fn
        #: batched rank read (analytics.sketch_known) — a wave's served
        #: keys at once, and a pass's victim candidates at once
        self.rank_batch = rank_batch
        self.promote_threshold = max(int(promote_threshold), 1)
        self.metrics = metrics
        self.recorder = recorder
        self._fault = fault
        self._skip_victim = skip_victim
        #: rank feed for fused-tap engines: their device tap gates out
        #: invalid rows, and cold rows ride the wave invalid — without
        #: this feed a cold key could never accrue admission rank.
        self._tap = tap
        self.cold_served = 0  # guarded-by: self._mu
        #: keys CREATED cold: a served row whose key no tier held (a
        #: first-seen key whose device bucket is full)
        self.cold_created = 0  # guarded-by: self._mu
        self.promotions = 0  # lock-free: resolve-path only (engine-lock serialized)
        self.demotions = 0  # lock-free: resolve-path only (engine-lock serialized)
        self.migrations_aborted = 0  # lock-free: resolve-path only (engine-lock serialized)
        #: admissions a pass's bound (MIGRATE_MAX) put off
        self.admissions_deferred = 0  # lock-free: resolve-path only (engine-lock serialized)
        engine.tier = self

    # ---- membership reads ----------------------------------------------

    def resident_mask(self, khash: np.ndarray) -> np.ndarray:
        """bool[n]: which of ``khash`` are cold-resident right now.
        The engine's pre-mask read — under the engine lock the answer
        stays true until the same call's resolve."""
        with self._mu:
            return self._store.contains_batch(khash)

    def cold_keys(self) -> int:
        with self._mu:
            return len(self._store)

    def mem_bytes(self) -> int:
        """Host bytes the cold tier holds (memory-ledger probe, ISSUE
        13): one 8-byte key plus the ROW_COLS int64 columns per row —
        exact for the native store, the Python-dict store's estimate
        uses the same row layout."""
        with self._mu:
            return len(self._store) * (len(ROW_COLS) + 1) * 8

    def stats(self) -> dict:
        with self._mu:
            return {"cold_keys": len(self._store),
                    "cold_served": self.cold_served,
                    "cold_created": self.cold_created,
                    "native": self._store.native,
                    "promotions": self.promotions,
                    "demotions": self.demotions,
                    "migrations_aborted": self.migrations_aborted,
                    "admissions_deferred": self.admissions_deferred}

    # ---- row handoff (seeding / snapshot / overflow) -------------------

    def peek_row(self, kh: int):
        """The key's cold row as a {col: int} dict, or None."""
        with self._mu:
            row = self._store.get(int(kh))
        if row is None:
            return None
        return dict(zip(ROW_COLS, row))

    def pop_row(self, kh: int):
        """Remove + return the key's cold row ({col: int} or None) —
        the mesh tier's pin seed path: the replica tier takes
        ownership, so the cold copy must not linger (a stale shadow
        would resurface after the pin retires)."""
        with self._mu:
            row = self._store.pop(int(kh))
        if row is None:
            return None
        return dict(zip(ROW_COLS, row))

    def put_row(self, kh: int, cols: dict) -> None:
        """Adopt one row (mesh demote overflow: the
        device table had no slot — before the tier this row was silently
        dropped)."""
        with self._mu:
            self._store.put(int(kh),
                            tuple(int(cols[f]) for f in ROW_COLS))
        self._gauge()

    def adopt_rows(self, arrays: dict, idx) -> int:
        """Adopt restore-overflow rows (store.py column arrays, row
        indices ``idx`` did not place on device) — restore's no-phantom
        contract: every snapshot row lands in exactly one tier.  ONE
        batch put (a key that comes twice keeps its last row); phase
        `restore.adopt`."""
        idx = np.asarray(idx, np.int64)
        with phase("restore.adopt", self.metrics):
            keys = np.asarray(arrays["key"], np.uint64)[idx]
            rows = np.empty((len(idx), len(ROW_COLS)), np.int64)
            for j, f in enumerate(ROW_COLS):
                rows[:, j] = np.asarray(arrays[f])[idx]
            with self._mu:
                self._store.put_batch(keys, rows)
        self._gauge()
        return len(idx)

    def snapshot_arrays(self) -> Optional[dict]:
        """Cold rows as store.py column arrays (key included), or None
        when empty — snapshot streams these alongside the device
        columns."""
        with self._mu:
            keys, rows = self._store.snapshot()
        if not len(keys):
            return None
        out = {"key": keys}
        for j, f in enumerate(ROW_COLS):
            col = rows[:, j]
            out[f] = col.astype(np.int32) if f == "meta" else col
        return out

    # ---- the resolve path ----------------------------------------------

    def resolve(self, engine, batch, khash: np.ndarray, now_ms: int,
                cols: tuple, cold_mask, orig_valid, mslot=None) -> tuple:
        """Serve every cold-lane row of a resolved wave: pre-masked
        cold-resident rows plus residual table-full rows (brand-new
        keys with the device table full → find-or-create here).  Runs
        inside ``check_packed`` under the engine lock; patches the five
        response columns in place and clears ``full``.

        Per-key requests apply in (arrival time, original index) order
        — the same lexicographic order the device's segment sort gives
        the hot tier, so duplicate-key batches keep sequential parity.
        Over the native store that is ONE C++ pass
        (``_NativeColdStore.apply_batch``), over the dict store the
        loop below; admission then reads the served keys' ranks once.
        """
        status, full = cols[0], cols[4]
        need = full & orig_valid if orig_valid is not None else full.copy()
        if cold_mask is not None:
            need = need | cold_mask
        if mslot is not None:
            need = need & (np.asarray(mslot) < 0)
        if not need.any():
            return cols
        # inside wave.scatter: its own ticks at both ends (phase.begin)
        timed = phase("tier.resolve", self.metrics).begin(
            at=time.perf_counter())
        idxs = np.nonzero(need)[0]
        req = [np.asarray(getattr(batch, f)) for f in _REQ_COLS]
        with self._mu:
            store = self._store
            native = store.apply_batch is not None
            if native:
                served, created, served_khs = store.apply_batch(
                    khash, idxs, req, now_ms, cols)
            else:
                served, created, served_khs = self._apply_rows(
                    store, khash, idxs, req, now_ms, cols)
            self.cold_served += served
            self.cold_created += created
        m = self.metrics
        if m is not None:
            m.tier_cold_serves.inc(served)
            if native:
                m.tier_cold_native_serves.inc(served)
            if created:
                m.tier_cold_creates.inc(created)
        self._gauge()
        if self._tap is not None:
            try:
                self._tap(khash[idxs], req[0][idxs], status[idxs])
            except Exception:  # pragma: no cover - analytics only
                log.exception("tier rank-feed tap")
        self._admit(engine, served_khs)
        timed.end(at=time.perf_counter())
        return cols

    @staticmethod
    def _apply_rows(store, khash, idxs, req, now_ms: int, cols) -> tuple:
        """The Python lane of ``resolve`` (any store): ``store.get`` →
        ``_host_apply`` → ``store.put`` a row, in (effective stamp,
        index) order.  What ``apply_batch`` returns."""
        status, lim_o, rem_o, rst_o, full = cols
        h_now = req[-1]

        def _eff_now(i: int) -> int:
            t = int(h_now[i])
            return t if t > 0 else int(now_ms)

        order = sorted(idxs.tolist(), key=lambda i: (_eff_now(i), i))
        served_khs = {}  # distinct, in order of first service
        created = 0
        for i in order:
            kh = int(khash[i])
            row = store.get(kh)
            if row is None:
                created += 1
            st, orem, rst, olim, new_row = _host_apply(
                row, *(int(c[i]) for c in req[:-1]), _eff_now(i))
            store.put(kh, new_row)
            status[i] = st
            rem_o[i] = orem
            rst_o[i] = rst
            lim_o[i] = olim
            full[i] = False
            served_khs[kh] = None
        return len(order), created, list(served_khs)

    # ---- admission / migration -----------------------------------------

    def _admit(self, engine, khs) -> None:
        """Hand the just-served cold keys (``khs``: distinct, in order
        of first service) whose rank clears the admission threshold to
        ONE migration pass.  No rank feed (analytics off) → no
        admission: serving stays exact, just host-paced.  The ranks are
        read ONCE a wave (``rank_batch``; a feed without it a key at a
        time).  A pass moves at most ``MIGRATE_MAX`` keys, the hottest:
        what is over stays cold and is admitted when next served."""
        if self.rank_fn is None or not len(khs):
            return
        khs = np.asarray(khs, np.uint64)
        ranks = self._ranks(khs)
        if ranks is None:
            return
        hot = np.nonzero(ranks >= self.promote_threshold)[0]
        if not hot.size:
            return
        if hot.size > MIGRATE_MAX:
            over = hot.size - MIGRATE_MAX
            hot = np.sort(hot[np.argsort(-ranks[hot],
                                         kind="stable")[:MIGRATE_MAX]])
            self.admissions_deferred += over
            if self.metrics is not None:
                self.metrics.tier_admissions_deferred.inc(over)
        self.migrate(engine, khs[hot], ranks[hot])

    def _ranks(self, khs: np.ndarray):
        """i64[n] rank of each key by the feed, or None where the feed
        fails (analytics only: nothing is admitted)."""
        try:
            if self.rank_batch is not None:
                return np.asarray(self.rank_batch(khs), np.int64)
            return np.fromiter((self.rank_fn(int(k)) for k in khs),
                               np.int64, count=len(khs))
        except Exception:  # pragma: no cover - analytics only
            return None

    def _faulted(self, point: str) -> bool:
        """The fault point ``point`` fired (chaos runs): this one row's
        migration is abandoned, the row stays in its source tier."""
        if self._fault is None:
            return False
        try:
            self._fault(point)
            return False
        except Exception:  # FaultInjected
            self.migrations_aborted += 1
            if self.metrics is not None:
                self.metrics.tier_migrations_aborted.inc()
            return True

    def migrate(self, engine, khs, ranks) -> int:
        """ONE migration pass: the cold rows of ``khs`` (ranks
        ``ranks``, both in the caller's order) moved to the device
        tier, each evicting the coldest resident row of its probe
        window back to host where no slot is free.  Returns the keys
        promoted.  Phase `tier.migrate`, one sample a pass.

        The device is read once and written once (``engine.tier_image``:
        the keys' distinct buckets, fetched behind whatever wave is
        already launched — so a victim's row holds what that wave did
        to it — and resolved on ONE host image, promotees that share a
        bucket too); the cold store gives the promotees' rows in one
        call, takes the victims' in one and drops the promotees in one.
        Conservation-exact: all eight value columns (including
        t_ms/created_at lineage and expire_at) move verbatim in both
        directions; a victim is adopted cold BEFORE the device write
        and a promotee dropped from the cold store AFTER it, so no
        failure between leaves a row in neither tier; runs under the
        engine lock, so no request can observe a key mid-flight.  A row
        outside the engine's step domain stays cold; a fault at
        `tier_promote` / `tier_demote` leaves that one row where it
        was."""
        timed = phase("tier.migrate", self.metrics).begin(
            at=time.perf_counter())
        try:
            return self._migrate(engine, np.asarray(khs, np.uint64),
                                 np.asarray(ranks, np.int64))
        finally:
            timed.end(at=time.perf_counter())

    def _migrate(self, engine, khs: np.ndarray, ranks: np.ndarray) -> int:
        _, first = np.unique(khs, return_index=True)
        if first.size != khs.size:  # a key twice: once, where it was first
            first.sort()
            khs, ranks = khs[first], ranks[first]
        with self._mu:
            keep, rows = self._store.take_batch(khs)
        admissible = getattr(engine, "tier_rows_admissible", None)
        if admissible is not None and keep.any():
            keep &= admissible(rows)  # outside the step domain (Pallas)
        if self._fault is not None:
            for i in np.nonzero(keep)[0]:
                if self._faulted("tier_promote"):
                    keep[i] = False
        if not keep.any():
            return 0
        khs, ranks, rows = khs[keep], ranks[keep], rows[keep]
        img = engine.tier_image(khs)
        every = np.arange(len(khs))
        placed = img.place(every, rows)
        demoted = np.empty(0, np.uint64)
        need = every[~placed]
        if need.size:
            victims = self._pick_victims(img, need, khs, ranks)
            need, victims = need[victims != 0], victims[victims != 0]
        if need.size:
            got, vrows = img.take(need, victims)
            need, demoted = need[got], victims[got]
            with self._mu:
                self._store.put_batch(demoted, vrows[got])
            placed[need] = img.place(need, rows[need])
        img.commit()
        promoted = khs[placed]
        with self._mu:
            self._store.take_batch(promoted, remove=True)
        self._count(promoted, ranks[placed], demoted)
        return len(promoted)

    def _count(self, promoted, ranks, demoted) -> None:
        """One pass's counters, once (``inc(n)``), and its
        flight-recorder events, capped."""
        self.promotions += len(promoted)
        self.demotions += len(demoted)
        m, rec = self.metrics, self.recorder
        if m is not None:
            if len(promoted):
                m.tier_promotions.inc(len(promoted))
            if len(demoted):
                m.tier_demotions.inc(len(demoted))
        if rec is not None:
            for kh, r in zip(promoted[:_EVENTS_A_PASS].tolist(),
                             ranks[:_EVENTS_A_PASS].tolist()):
                rec.record("tier_promote", khash=f"0x{kh:016x}", rank=r,
                           of_pass=len(promoted))
            for kh in demoted[:_EVENTS_A_PASS].tolist():
                rec.record("tier_demote", khash=f"0x{kh:016x}",
                           of_pass=len(demoted))
        self._gauge()

    def _pick_victims(self, img, need: np.ndarray, khs: np.ndarray,
                      ranks: np.ndarray) -> np.ndarray:
        """u64[k]: for each promotee ``need`` (indices into ``khs``)
        that found no free slot, the resident key of its probe window
        to evict, 0 where there is none.  The coldest by the rank feed,
        and STRICTLY colder than its promotee by that same measure;
        the LAST of its window among equals (a restore gives a bucket's
        slots to its rows in snapshot order, hottest first: among keys
        the feed cannot tell apart the last slot holds the coldest);
        never a key of this pass (its row was just placed), never one
        another promotee of the pass already took, never a
        replica-pinned key (its device row is the home copy of
        coherence machinery above us), and none at all where a fault
        fires at `tier_demote`.  Promotees choose in the caller's
        order, so two that share a window take its two coldest.  The
        candidates' ranks are ONE read of the feed."""
        occ = img.occupants(need)  # [k, window]
        out = np.zeros(len(need), np.uint64)
        cand = self._ranks(occ.reshape(-1))
        if cand is None:
            return out
        never = np.iinfo(np.int64).max
        cand = np.where((occ == 0) | np.isin(occ, khs), never,
                        cand.reshape(occ.shape))
        cand[cand >= ranks[need][:, None]] = never  # not strictly colder
        skip = self._skip_victim
        open_ = np.ones(len(need), bool)
        while open_.any():
            at = np.nonzero(open_)[0]
            # (the LAST of the minimum: argmin over the window reversed)
            best = occ.shape[1] - 1 - cand[at, ::-1].argmin(axis=1)
            some = cand[at, best] != never
            open_[at[~some]] = False  # everything left is at least as hot
            at, best = at[some], best[some]
            pick = occ[at, best]
            # the first promotee to ask for a key has it; the others
            # look again without it
            gone = []
            for j in np.sort(np.unique(pick, return_index=True)[1]):
                r, v = at[j], pick[j]
                if skip is not None and skip(int(v)):
                    gone.append(v)  # pinned: nobody's, r looks again
                    continue
                open_[r] = False
                if not self._faulted("tier_demote"):
                    out[r] = v
                    gone.append(v)
            if gone:
                cand[np.isin(occ, gone)] = never
        return out

    def promote(self, engine, kh: int, rank: int) -> bool:
        """``migrate`` for one key (the tests' and the chaos tools'
        door): True where the key's row moved to the device tier."""
        return self.migrate(engine, [kh], [rank]) > 0

    def demote(self, engine, kh: int) -> bool:
        """One device row moved back to the cold tier, a cap-overflow
        demotion: the eviction half of a pass, on its own image.
        Byte-exact handoff; under the engine lock."""
        if self._faulted("tier_demote"):
            return False
        karr = np.array([kh], np.uint64)
        img = engine.tier_image(karr)
        got, vrows = img.take(np.zeros(1, np.int64), karr)
        if not got[0]:
            return False
        with self._mu:
            self._store.put_batch(karr, vrows)
        img.commit()
        self._count(karr[:0], karr[:0], karr)
        return True

    def _gauge(self) -> None:
        m = self.metrics
        if m is not None:
            with self._mu:
                n = len(self._store)
            m.tier_cold_keys.set(n)
