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
- when a cold key's heavy-hitter rank (analytics.py sketch) clears the
  admission threshold its row migrates to HBM, evicting the coldest
  resident row of its probe window back to host under a
  conservation-exact, created_at-preserving handoff (all eight value
  columns move verbatim, both directions).

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
        #: batched rank read (analytics.sketch_counts) — victim
        #: selection scans a whole probe window per promotion
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
                    "migrations_aborted": self.migrations_aborted}

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
        """Promote every just-served cold key (``khs``: distinct, in
        order of first service) whose sketch rank clears the admission
        threshold.  No rank feed (analytics off) → no admission: serving
        stays exact, just host-paced.  The ranks are read ONCE a wave
        where the feed has a batched read (``rank_batch``), a key at a
        time otherwise.  Phase `tier.migrate`: one sample an admission
        tried, victim pick and demotion inside."""
        if self.rank_fn is None or not len(khs):
            return
        thr = self.promote_threshold
        if self.rank_batch is not None:
            try:
                ranks = np.asarray(self.rank_batch(khs))
            except Exception:  # pragma: no cover - analytics only
                return
            hot = ((int(khs[j]), int(ranks[j]))
                   for j in np.nonzero(ranks >= thr)[0])
        else:
            hot = self._ranked_over(khs, thr)
        for kh, r in hot:
            timed = phase("tier.migrate", self.metrics).begin(
                at=time.perf_counter())
            self.promote(engine, kh, r)
            timed.end(at=time.perf_counter())

    def _ranked_over(self, khs, thr: int):
        """(key, rank) of ``khs`` at or over ``thr``, read a key at a
        time as each is reached (``rank_fn`` alone: the tests' feeds)."""
        for kh in khs:
            try:
                r = self.rank_fn(int(kh))
            except Exception:  # pragma: no cover - analytics only
                return
            if r >= thr:
                yield int(kh), r

    def promote(self, engine, kh: int, rank: int) -> bool:
        """Migrate one cold row to the device tier, evicting the
        coldest resident row of its probe window back to host when no
        slot is free.  Conservation-exact: all eight value columns
        (including t_ms/created_at lineage and expire_at) move verbatim
        in both directions; runs under the engine lock, so no request
        can observe the key mid-flight."""
        with self._mu:
            row = self._store.get(int(kh))
        if row is None:
            return False
        if not getattr(engine, "tier_row_admissible", _always)(row):
            return False  # outside the engine's step domain (Pallas)
        try:
            if self._fault is not None:
                self._fault("tier_promote")
        except Exception:  # FaultInjected: admission aborts, row stays cold
            self.migrations_aborted += 1
            if self.metrics is not None:
                self.metrics.tier_migrations_aborted.inc()
            return False
        karr = np.array([kh], np.uint64)
        if not self._upsert(engine, karr, row):
            victim = self._pick_victim(engine, kh, rank)
            if victim is None:
                return False
            if not self.demote(engine, victim):
                return False
            if not self._upsert(engine, karr, row):
                # the freed slot is in kh's own probe window, so this
                # is unreachable; tolerate it without losing the row
                return False
        with self._mu:
            self._store.pop(int(kh))
        self.promotions += 1
        if self.metrics is not None:
            self.metrics.tier_promotions.inc()
        if self.recorder is not None:
            self.recorder.record("tier_promote", khash=f"0x{kh:016x}",
                                 rank=int(rank))
        self._gauge()
        return True

    def demote(self, engine, kh: int) -> bool:
        """Migrate one device row back to the cold tier (eviction half
        of an admission, or a cap-overflow demotion): gather the row,
        adopt it cold, then clear the device slot.  Byte-exact handoff;
        under the engine lock."""
        try:
            if self._fault is not None:
                self._fault("tier_demote")
        except Exception:  # FaultInjected: eviction aborts
            self.migrations_aborted += 1
            if self.metrics is not None:
                self.metrics.tier_migrations_aborted.inc()
            return False
        karr = np.array([kh], np.uint64)
        found, vcols = engine.gather_rows(karr)
        if not found[0]:
            return False
        row = tuple(int(vcols[f][0]) for f in ROW_COLS)
        with self._mu:
            self._store.put(int(kh), row)
        engine.remove_rows(karr)
        self.demotions += 1
        if self.metrics is not None:
            self.metrics.tier_demotions.inc()
        if self.recorder is not None:
            self.recorder.record("tier_demote", khash=f"0x{kh:016x}")
        self._gauge()
        return True

    def _pick_victim(self, engine, kh: int, rank: int):
        """The coldest (minimum sketch rank) resident key in ``kh``'s
        probe window — strictly colder than the promotee, never a
        replica-pinned key (its device row is the home copy of tiered
        coherence machinery above us)."""
        probe = getattr(engine, "probe_occupant_keys", None)
        if probe is None or self.rank_fn is None:
            return None
        occ = probe(int(kh))
        skip = self._skip_victim
        cands = []
        for k in occ:
            ik = int(k)
            if ik == 0 or ik == int(kh):
                continue
            if skip is not None and skip(ik):
                continue
            cands.append(ik)
        if not cands:
            return None
        if self.rank_batch is not None:  # one sketch-lock acquisition
            ranks = self.rank_batch(cands)
        else:
            ranks = [self.rank_fn(k) for k in cands]
        best = min(range(len(cands)), key=ranks.__getitem__)
        if ranks[best] >= rank:
            return None  # everything resident is at least as hot
        return cands[best]

    @staticmethod
    def _upsert(engine, karr: np.ndarray, row) -> bool:
        cols = {}
        for f, v in zip(ROW_COLS, row):
            cols[f] = np.array([v], np.int32 if f == "meta" else np.int64)
        return int(engine.upsert_rows(karr, cols)) > 0

    def _gauge(self) -> None:
        m = self.metrics
        if m is not None:
            with self._mu:
                n = len(self._store)
            m.tier_cold_keys.set(n)


def _always(_row) -> bool:
    return True
