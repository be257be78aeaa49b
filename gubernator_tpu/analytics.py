"""Key-level analytics: heavy-hitter ledger + per-phase latency ledger.

ISSUE 4: after the wave telemetry of ISSUE 1 the serving loop's
*aggregate* health is visible, but not WHICH keys are hot, which drive
OVER_LIMIT, or where a request's milliseconds go between ingest, queue,
device and peer forward.  Hot-key skew is the dominant failure mode of
distributed limiters (PAPERS.md), and the tiered store's admission
and the mesh tier's overflow policy (tiering.py, instance.py ›
_mesh_overflow_victim) rank keys by exactly this hotness signal.

Two pieces, both bounded-memory and OFF the caller's critical path:

- ``HeavyHitterSketch``: a columnar Space-Saving ledger of ``width``
  counters (GUBER_SKETCH_WIDTH, default 4×K) reporting the top ``K``
  keys (GUBER_TOPK, default 256).  Exact when the key domain fits in
  ``width``; otherwise every reported count over-estimates by at most
  its per-key ``err`` field, itself bounded by ``total_weight/width``
  (the classic Space-Saving guarantee).  Per key it tracks hits,
  OVER_LIMIT count, last-seen wall time, and the key NAME when a wave
  carried one (object-lane taps; pure-columnar wire waves only know
  the 64-bit khash).

- ``PhaseLedger``: per-phase duration attribution (every name of
  ``tracing.PHASE_CATALOG``; ``tracing.phase`` is what feeds it)
  behind both the ``gubernator_phase_duration{phase=...}`` histograms
  and the ``GET /debug/phases`` percentile snapshot.  The in-wave phases
  (pack, device, resolve) partition the existing
  ``gubernator_dispatcher_wave_duration`` exactly (asserted by
  tests/test_telemetry.py).

``KeyAnalytics`` owns both plus the tap queue: the dispatcher enqueues
cheap column COPIES after each wave resolves, and a single worker
thread does all unique/aggregate/sketch work, draining the queue in
paced batches (one vectorized fold per ``BATCH_INTERVAL_S`` window) —
a full queue drops the wave (counted) rather than ever blocking a
caller.
"""
from __future__ import annotations

import json
import os
import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from .hashing import mix64_np
from .tracing import phase

#: phase label set (OBSERVABILITY.md › phase catalog).  ``IN_WAVE``
#: phases partition wave_duration; the rest attribute time outside the
#: wave (job queue wait, wire ingest, response build, peer flush).
IN_WAVE_PHASES = ("pack", "device", "resolve")
PHASES = ("ingest", "pack", "queue_wait", "device", "resolve", "build",
          "peer_flush", "broadcast", "snapshot", "restore",
          "global_fold")


def _env_int(name: str, default: int, lo: int = 1) -> int:
    raw = os.environ.get(name, "")
    if raw:
        try:
            return max(int(raw), lo)
        except ValueError:
            pass  # malformed: keep the default
    return default


class HeavyHitterSketch:
    """Space-Saving heavy hitters over 64-bit key hashes, columnar.

    ``width`` counters total; ``topk()`` reports the heaviest ``k``.
    Storage is parallel numpy columns (count/err/over/last/khash) with
    a sorted-hash index rebuilt lazily per wave, so a whole wave folds
    in with vectorized ops — no per-key Python loop on the columnar
    path (the dict-of-slots + min-scan variant cost ~40 ms per
    1000-req Zipf wave; this is ~0.2 ms, which matters on small hosts
    where the worker thread competes with serving for cores).

    Admission when full follows EXACT sequential Space-Saving
    semantics (each newcomer evicts the then-minimum slot and inherits
    its count as the overestimate bound ``err``), simulated for a
    whole wave with a sorted-victims/FIFO merge instead of a heap —
    see the comment at the admission step.  The classic guarantees
    hold, all deterministic:

    - exact (every ``err`` == 0) while the observed key domain fits in
      ``width``;
    - per tracked key: ``true <= count`` and ``count - true <= err``;
    - tracked counts sum to ``total_weight`` exactly, hence
      ``err <= error_bound()`` (the current minimum)
      ``<= total_weight/width`` by pigeonhole — and any key whose true
      count exceeds ``total_weight/width`` is guaranteed tracked.

    NOT thread-safe: KeyAnalytics serializes access on its worker
    thread (snapshot readers take its lock).
    """

    def __init__(self, k: int = 256, width: Optional[int] = None):
        self.k = max(int(k), 1)
        self.width = max(int(width) if width else 4 * self.k, self.k)
        w = self.width
        self._cnt = np.zeros(w, np.int64)
        self._err = np.zeros(w, np.int64)
        self._over = np.zeros(w, np.int64)
        self._last = np.zeros(w, np.int64)
        self._kh = np.zeros(w, np.uint64)
        self._used = 0
        self._sorted_kh = np.empty(0, np.uint64)
        self._sorted_slot = np.empty(0, np.int64)
        self._dirty = False  # membership changed since last reindex
        self.total_weight = 0
        #: bounded khash → "name_unique_key" side table: names seen on
        #: object-lane waves resolve keys that later go hot through the
        #: columnar wire lanes (which only carry hashes)
        self._names: Dict[int, str] = {}
        self._names_cap = max(8 * self.width, 4096)

    def __len__(self) -> int:
        return self._used

    # ---- ingest ---------------------------------------------------------

    def _reindex(self) -> None:
        if self._dirty or self._sorted_kh.size != self._used:
            order = np.argsort(self._kh[:self._used])
            self._sorted_kh = self._kh[:self._used][order]
            self._sorted_slot = order.astype(np.int64)
            self._dirty = False

    def update(self, khash: np.ndarray, hits: np.ndarray,
               over: np.ndarray, t_ms: int,
               names: Optional[List[Optional[str]]] = None) -> None:
        """Fold one wave's columns in.  ``khash`` uint64, ``hits``
        weights (clamped >= 1 so hits=0 status queries still register
        presence), ``over`` truthy where the decision was OVER_LIMIT.
        ``names``, when given, aligns with ``khash``."""
        n = len(khash)
        if n == 0:
            return
        w = np.maximum(np.asarray(hits, np.int64), 1)
        kh = np.asarray(khash, np.uint64)
        ob = np.asarray(over, bool)
        # sort-and-reduceat aggregation (np.unique + ufunc.at is ~2×
        # slower; this update is the analytics worker's hot loop).
        # Weight-1 waves — the common columnar shape — skip the
        # argsort permutation entirely: counts are plain run lengths
        # of the sorted hashes, and the (sparse) over-limit rows
        # aggregate separately and scatter in by binary search.
        if names is None and int(w.max()) == 1:
            ks = np.sort(kh)
            starts = np.nonzero(np.concatenate(
                ([True], ks[1:] != ks[:-1])))[0]
            uniq = ks[starts]
            wsum = np.diff(np.append(starts, ks.size))
            osum = np.zeros(uniq.size, np.int64)
            if ob.any():
                kho = np.sort(kh[ob])
                so = np.nonzero(np.concatenate(
                    ([True], kho[1:] != kho[:-1])))[0]
                osum[np.searchsorted(uniq, kho[so])] = \
                    np.diff(np.append(so, kho.size))
        else:
            o = ob.astype(np.int64)
            sort = np.argsort(kh, kind="stable")
            ks = kh[sort]
            starts = np.nonzero(np.concatenate(
                ([True], ks[1:] != ks[:-1])))[0]
            uniq = ks[starts]
            wsum = np.add.reduceat(w[sort], starts)
            osum = np.add.reduceat(o[sort], starts)
            if names is not None:
                # object-lane waves only (small): remember each unique
                # key's name so columnar taps resolve it at report time
                rep = sort[starts]  # any occurrence names the key
                for j in range(uniq.size):
                    name = names[int(rep[j])]
                    if name is not None:
                        self._note_name(int(uniq[j]), name)
        self.total_weight += int(wsum.sum())
        # tracked keys: one sorted-membership probe, vectorized folds
        self._reindex()
        if self._sorted_kh.size:
            pos = np.minimum(np.searchsorted(self._sorted_kh, uniq),
                             self._sorted_kh.size - 1)
            tracked = self._sorted_kh[pos] == uniq
            slots = self._sorted_slot[pos[tracked]]
            self._cnt[slots] += wsum[tracked]
            self._over[slots] += osum[tracked]
            self._last[slots] = t_ms
        else:
            tracked = np.zeros(uniq.size, bool)
        m = int(uniq.size - tracked.sum())
        if m == 0:
            return
        new_kh = uniq[~tracked]
        new_w = wsum[~tracked]
        new_o = osum[~tracked]
        free = self.width - self._used
        if free > 0:
            take = min(free, m)
            sl = np.arange(self._used, self._used + take)
            self._kh[sl] = new_kh[:take]
            self._cnt[sl] = new_w[:take]
            self._err[sl] = 0
            self._over[sl] = new_o[:take]
            self._last[sl] = t_ms
            self._used += take
            self._dirty = True
            if take == m:
                return
            new_kh, new_w, new_o = (new_kh[take:], new_w[take:],
                                    new_o[take:])
            m -= take
        # EXACT sequential Space-Saving admission (each newcomer
        # evicts the then-minimum slot and inherits its count as the
        # error bound).  Arrival order within a wave is ours to
        # choose, so split by weight: the few heavy newcomers run the
        # exact two-way merge; the weight-1 tail — the dominant churn
        # shape — admits via closed-form water-filling with no
        # per-item loop at all.  Either way the counts sum to the
        # total observed weight, hence err <= min <= total/width.
        heavy = new_w > 1
        if heavy.any():
            self._admit_merge(new_kh[heavy], new_w[heavy],
                              new_o[heavy], t_ms)
        light = ~heavy
        if light.any():
            self._admit_level(new_kh[light], new_o[light], t_ms)

    def _admit_merge(self, new_kh, new_w, new_o, t_ms: int) -> None:
        """Sequential Space-Saving for arbitrary weights, simulated as
        a two-way merge: processing newcomers in ascending-weight
        order makes both the popped minima v_1 <= v_2 <= ... and the
        re-inserted values v_j + w_j nondecreasing, so the "heap" is
        just the sorted victim counts + a FIFO of intra-wave
        re-insertions.  A slot popped from the FIFO re-evicts an
        earlier newcomer of this same wave (its assignment is simply
        overwritten).  Evicted keys' over-limit tallies do NOT carry
        over, so `over` stays exact per tracked period."""
        order = np.argsort(new_w, kind="stable")
        new_kh, new_w, new_o = new_kh[order], new_w[order], new_o[order]
        sort_idx = np.argsort(self._cnt[: self._used])
        scnt = self._cnt[: self._used][sort_idx].tolist()
        sslot = sort_idx.tolist()
        ns = len(scnt)
        si = qi = 0
        qv: list = []  # FIFO as append-only lists + head index (qi):
        qs: list = []  # stays sorted, so no heap is ever needed
        assign: Dict[int, int] = {}  # slot → newcomer idx (last wins)
        inherited: Dict[int, int] = {}  # slot → evicted count
        for j, wj in enumerate(new_w.tolist()):
            if qi < len(qv) and (si >= ns or qv[qi] <= scnt[si]):
                v, slot = qv[qi], qs[qi]
                qi += 1
            else:
                v, slot = scnt[si], sslot[si]
                si += 1
            assign[slot] = j
            inherited[slot] = v
            qv.append(v + wj)
            qs.append(slot)
        slots = np.fromiter(assign.keys(), np.int64, len(assign))
        js = np.fromiter(assign.values(), np.int64, len(assign))
        vs = np.fromiter(inherited.values(), np.int64, len(inherited))
        self._kh[slots] = new_kh[js]
        self._cnt[slots] = vs + new_w[js]
        self._err[slots] = vs
        self._over[slots] = new_o[js]
        self._last[slots] = t_ms
        self._dirty = True

    def _admit_level(self, new_kh, new_o, t_ms: int) -> None:
        """Weight-1 newcomers via exact water-filling: s pops of
        "evict the minimum, reinsert min+1" ARE s increments of the
        global minimum, so the final counts are the level-fill of the
        sorted counts — raise the lowest t0 counts to a common level L
        (the first r of them to L+1) — computed in closed form.
        Raised slots take newcomer keys with err = count - 1; the
        s - raised singletons admitted-then-re-evicted inside the wave
        vanish, exactly as sequential processing would have them."""
        s = len(new_kh)
        used = self._used
        cnt = self._cnt[:used]
        order = np.argsort(cnt)
        c = cnt[order]
        csum = np.cumsum(c)
        # cost[i] = lifting slots 0..i to level c[i]; nondecreasing
        cost = (np.arange(1, used + 1) * c) - csum
        t0 = int(np.searchsorted(cost, s, side="right"))
        pool = s + int(csum[t0 - 1])
        level = pool // t0
        r = pool - level * t0
        newvals = np.full(t0, level, np.int64)
        newvals[:r] += 1
        changed = newvals > c[:t0]
        nraised = int(changed.sum())
        slots = order[:t0][changed]
        self._cnt[slots] = newvals[changed]
        self._err[slots] = newvals[changed] - 1
        self._kh[slots] = new_kh[:nraised]
        self._over[slots] = new_o[:nraised]
        self._last[slots] = t_ms
        self._dirty = True

    def _note_name(self, kh: int, name: str) -> None:
        names = self._names
        if kh not in names and len(names) >= self._names_cap:
            # bounded: drop an arbitrary half when full (plain dicts
            # pop in insertion order, so this sheds the oldest names)
            for old in list(names)[: self._names_cap // 2]:
                del names[old]
        names[kh] = name

    # ---- reporting ------------------------------------------------------

    def error_bound(self) -> int:
        """Worst-case overestimate for a newly admitted key: the
        current minimum tracked count (<= total_weight/width).  0
        while the ledger has free slots (everything exact)."""
        if self._used < self.width:
            return 0
        return int(self._cnt[: self._used].min())

    def count_of(self, khash: int) -> int:
        """Tracked count for one key hash (0 when untracked) — the
        rank the mesh tier picks its overflow victim by.  An
        overestimate by at most the key's ``err`` (the tiered store
        admits by ``known_of``, which takes it off)."""
        self._reindex()
        if not self._sorted_kh.size:
            return 0
        kh = np.uint64(khash)
        pos = int(np.searchsorted(self._sorted_kh, kh))
        if pos >= self._sorted_kh.size or self._sorted_kh[pos] != kh:
            return 0
        return int(self._cnt[self._sorted_slot[pos]])

    def _read(self, khashes, col) -> np.ndarray:
        """i64[n]: ``col(slots)`` for the tracked keys of an array, 0
        where untracked — ONE vectorised probe of the sorted index."""
        kh = np.asarray(khashes, np.uint64)
        out = np.zeros(kh.size, np.int64)
        self._reindex()
        if not self._sorted_kh.size or not kh.size:
            return out
        pos = np.minimum(np.searchsorted(self._sorted_kh, kh),
                         self._sorted_kh.size - 1)
        hit = self._sorted_kh[pos] == kh
        out[hit] = col(self._sorted_slot[pos[hit]])
        return out

    def counts_of(self, khashes) -> np.ndarray:
        """``count_of`` for every key hash of an array — i64[n], 0
        where untracked."""
        return self._read(khashes, lambda slot: self._cnt[slot])

    def known_of(self, khashes) -> np.ndarray:
        """``count - err`` for every key hash of an array — i64[n], 0
        where untracked: the hits each key is KNOWN to have drawn since
        the sketch began to track it (a tracked key's true count lies
        in ``[count - err, count]``).  Where the key domain is larger
        than ``width`` a newcomer inherits the evicted minimum as both
        its count and its ``err``, so under load EVERY tracked key's
        ``count`` reads in the thousands and says "tracked right now";
        ``count - err`` says "drew this many hits while tracked", which
        is what the tiered store admits and picks victims by
        (tiering.py › _admit reads a wave's ~1,100 served keys at
        once).  Equal to ``counts_of`` while the domain fits in
        ``width`` (every ``err`` is 0)."""
        return self._read(khashes,
                          lambda slot: self._cnt[slot] - self._err[slot])

    def topk(self, k: Optional[int] = None) -> List[dict]:
        k = self.k if k is None else max(int(k), 1)
        k = min(k, self._used)
        cnt = self._cnt[: self._used]
        if k < self._used:
            part = np.argpartition(cnt, self._used - k)[self._used - k:]
            order = part[np.argsort(cnt[part])[::-1]]
        else:
            order = np.argsort(cnt)[::-1]
        out = []
        for s in order[:k]:
            kh = int(self._kh[s])
            out.append({"khash": kh, "key": self._names.get(kh),
                        "hits": int(self._cnt[s]),
                        "err": int(self._err[s]),
                        "over_limit": int(self._over[s]),
                        "last_seen_ms": int(self._last[s])})
        return out

    # ---- fleet merge surface (ISSUE 19) ---------------------------------

    def merge_entries(self, entries: List[dict],
                      total_weight: Optional[int] = None) -> None:
        """Fold another sketch's REPORTED rows (``topk()`` dicts, khash
        as int or ``0x…`` hex) into this one — the fleet watchtower's
        merge surface.  Reuses the exact two-way Space-Saving merge:
        tracked keys add counts AND error bounds; untracked keys fill
        free slots (keeping their remote ``err``) or run
        ``_admit_merge``, after which the remote ``err`` of each
        SURVIVING newcomer is added on top of the inherited eviction
        bound.  The merged sketch obeys the summed-stream guarantee:
        ``true <= count`` and ``count - true <= err`` against the union
        stream.  When both sides saw disjoint key sets that fit in
        ``width`` the merge is exact (all ``err`` unchanged), which is
        what the fleet byte-equality test pins."""
        rows = []
        for e in entries:
            kh = e.get("khash")
            if isinstance(kh, str):
                kh = int(kh, 16)
            hits = int(e.get("hits", 0))
            if hits <= 0:
                continue
            rows.append((int(kh), hits, int(e.get("err", 0)),
                         int(e.get("over_limit", 0)),
                         int(e.get("last_seen_ms", 0)),
                         e.get("key")))
        if total_weight is not None:
            self.total_weight += int(total_weight)
        elif rows:
            self.total_weight += sum(r[1] for r in rows)
        if not rows:
            return
        kh = np.array([r[0] for r in rows], np.uint64)
        w = np.array([r[1] for r in rows], np.int64)
        er = np.array([r[2] for r in rows], np.int64)
        ov = np.array([r[3] for r in rows], np.int64)
        ls = np.array([r[4] for r in rows], np.int64)
        for r in rows:
            if r[5] is not None:
                self._note_name(r[0], r[5])
        # aggregate duplicate khashes (defensive: topk() never repeats
        # a hash, but merged docs from a retrying fetcher might)
        sort = np.argsort(kh, kind="stable")
        ks = kh[sort]
        starts = np.nonzero(np.concatenate(
            ([True], ks[1:] != ks[:-1])))[0]
        uniq = ks[starts]
        wsum = np.add.reduceat(w[sort], starts)
        ersum = np.add.reduceat(er[sort], starts)
        ovsum = np.add.reduceat(ov[sort], starts)
        lsmax = np.maximum.reduceat(ls[sort], starts)
        # tracked probe: counts add, error bounds add (both remotes'
        # overestimates can stack on the same key)
        self._reindex()
        if self._sorted_kh.size:
            pos = np.minimum(np.searchsorted(self._sorted_kh, uniq),
                             self._sorted_kh.size - 1)
            tracked = self._sorted_kh[pos] == uniq
            slots = self._sorted_slot[pos[tracked]]
            self._cnt[slots] += wsum[tracked]
            self._err[slots] += ersum[tracked]
            self._over[slots] += ovsum[tracked]
            np.maximum.at(self._last, slots, lsmax[tracked])
        else:
            tracked = np.zeros(uniq.size, bool)
        if int(tracked.sum()) == uniq.size:
            return
        new_kh = uniq[~tracked]
        new_w = wsum[~tracked]
        new_er = ersum[~tracked]
        new_o = ovsum[~tracked]
        new_ls = lsmax[~tracked]
        free = self.width - self._used
        if free > 0:
            take = min(free, len(new_kh))
            sl = np.arange(self._used, self._used + take)
            self._kh[sl] = new_kh[:take]
            self._cnt[sl] = new_w[:take]
            self._err[sl] = new_er[:take]  # keep the remote bound
            self._over[sl] = new_o[:take]
            self._last[sl] = new_ls[:take]
            self._used += take
            self._dirty = True
            if take == len(new_kh):
                return
            new_kh, new_w, new_er, new_o, new_ls = (
                new_kh[take:], new_w[take:], new_er[take:],
                new_o[take:], new_ls[take:])
        t_ms = int(new_ls.max())
        self._admit_merge(new_kh, new_w, new_o, t_ms)
        # surviving newcomers inherited an eviction bound from
        # _admit_merge; their remote err stacks on top (the remote
        # count they brought was itself an overestimate)
        self._reindex()
        pos = np.minimum(np.searchsorted(self._sorted_kh, new_kh),
                         self._sorted_kh.size - 1)
        alive = self._sorted_kh[pos] == new_kh
        slots = self._sorted_slot[pos[alive]]
        self._err[slots] += new_er[alive]
        np.maximum.at(self._last, slots, new_ls[alive])

    def canonical_bytes(self) -> bytes:
        """Deterministic byte form of the tracked state — khash-sorted
        ``(khash, cnt, err, over)`` rows as JSON.  ``last_seen_ms`` is
        a wall-clock artifact, not sketch state, so it is excluded;
        two sketches that tracked the same multiset of decisions
        byte-equal regardless of when they saw them (the fleet
        merge-exactness pin in tests/test_fleet.py)."""
        u = self._used
        rows = sorted(zip(self._kh[:u].tolist(),
                          self._cnt[:u].tolist(),
                          self._err[:u].tolist(),
                          self._over[:u].tolist()))
        return json.dumps({"width": self.width, "k": self.k,
                           "total_weight": self.total_weight,
                           "rows": rows},
                          separators=(",", ":")).encode()


class PhaseLedger:
    """Thread-safe per-phase duration aggregation: cumulative count/sum
    plus a bounded recent-sample window for percentile snapshots
    (prometheus histograms can't answer percentile queries)."""

    def __init__(self, maxlen: int = 4096):
        self._mu = threading.Lock()
        self._agg: Dict[str, list] = {}  # phase → [count, total_s]
        self._recent: Dict[str, deque] = {}
        self._maxlen = maxlen

    def observe(self, phase: str, seconds: float) -> None:
        with self._mu:
            a = self._agg.get(phase)
            if a is None:
                a = self._agg[phase] = [0, 0.0]
                self._recent[phase] = deque(maxlen=self._maxlen)
            a[0] += 1
            a[1] += seconds
            self._recent[phase].append(seconds)

    def mean(self, phase: str) -> Optional[float]:
        """Cheap mean seconds per sample for one phase (None before any
        sample) — the dispatcher's admission control projects queue
        waits from these (ISSUE 5) without paying snapshot()'s
        percentile math."""
        with self._mu:
            a = self._agg.get(phase)
            return (a[1] / a[0]) if a and a[0] else None

    def recent_p99(self, phase: str) -> Optional[float]:
        """p99 seconds over the bounded recent window of one phase
        (None before any sample) — the SLO engine's decision-latency
        feed; cheaper than a full snapshot() every tick."""
        with self._mu:
            d = self._recent.get(phase)
            if not d:
                return None
            xs = np.asarray(d, float)
        return float(np.percentile(xs, 99))

    def snapshot(self) -> Dict[str, dict]:
        with self._mu:
            out = {}
            for phase, (count, total) in self._agg.items():
                xs = np.asarray(self._recent[phase], float)
                out[phase] = {
                    "count": count,
                    "total_ms": round(total * 1e3, 3),
                    "p50_ms": round(float(np.percentile(xs, 50)) * 1e3, 4),
                    "p99_ms": round(float(np.percentile(xs, 99)) * 1e3, 4),
                    "max_ms": round(float(xs.max()) * 1e3, 4),
                }
            return out


def _read_varint(data, pos: int):
    shift = result = 0
    while True:
        b = data[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7
        if shift > 63:
            raise ValueError("varint overflow")


def _skip_field(data, wt: int, pos: int) -> int:
    if wt == 0:
        _, pos = _read_varint(data, pos)
    elif wt == 1:
        pos += 8
    elif wt == 5:
        pos += 4
    else:
        raise ValueError(f"wire type {wt}")
    return pos


def iter_wire_names(data) -> List[tuple]:
    """(name, unique_key) per request TLV of a serialized
    GetRateLimitsReq — a tolerant pure-Python walk (field 1 = repeated
    RateLimitReq; inside it field 1 = name, field 2 = unique_key).
    The analytics worker hands it ONE request TLV for each rate-limit
    name it has never seen (``KeyAnalytics._learn``); whole messages
    only on the shed path (``instance.py › _tenant_of_wire``)."""
    out: List[tuple] = []
    pos, end = 0, len(data)
    while pos < end:
        tag, pos = _read_varint(data, pos)
        if tag & 7 != 2:
            pos = _skip_field(data, tag & 7, pos)
            continue
        ln, pos = _read_varint(data, pos)
        body_end = pos + ln
        if tag >> 3 == 1:
            name = uniq = ""
            p = pos
            while p < body_end:
                t, p = _read_varint(data, p)
                if t & 7 != 2:
                    p = _skip_field(data, t & 7, p)
                    continue
                sl, p = _read_varint(data, p)
                if t >> 3 == 1:
                    name = bytes(data[p:p + sl]).decode("utf-8",
                                                        "replace")
                elif t >> 3 == 2:
                    uniq = bytes(data[p:p + sl]).decode("utf-8",
                                                        "replace")
                p += sl
            if name:
                out.append((name, uniq))
        pos = body_end
    return out


class TenantLedger:
    """Bounded-cardinality per-tenant RED ledger (ISSUE 11).

    A tenant IS a key prefix (ROADMAP › multi-tenant QoS): the id is
    the key name up to the first ``delim`` (the whole name when the
    delimiter is absent).  At most ``max_tenants`` distinct ids get
    their own bucket; every later newcomer folds into ``__other__``
    (bucket 0), so label cardinality, memory, and the /debug/tenants
    payload are all bounded no matter how adversarial the key mix is.

    Conservation is structural, not statistical: every attributed row
    lands in EXACTLY one bucket (a real tenant, or ``__other__`` for
    overflow and unresolvable khashes), so per-tenant counts sum to
    the totals row exactly — asserted under the 16-thread chaos soak
    by tests/test_slo_tenants.py.

    Thread-safe (own leaf lock); the analytics worker does the bulk
    vectorized folds, flag taps trickle in from serving threads.
    """

    OTHER = "__other__"
    FIELDS = ("requests", "hits", "over_limit", "errors", "degraded",
              "shed")

    def __init__(self, delim: Optional[str] = None,
                 max_tenants: Optional[int] = None):
        if delim is None:
            delim = os.environ.get("GUBER_TENANT_DELIM", "/") or "/"
        self.delim = delim
        self.max_tenants = (max_tenants if max_tenants is not None
                            else _env_int("GUBER_TENANT_MAX", 64))
        self._mu = threading.Lock()
        self._idx: Dict[str, int] = {self.OTHER: 0}  # guarded-by: self._mu
        self._tenant_names: List[str] = [self.OTHER]  # guarded-by: self._mu
        #: per-bucket [requests, hits, over, errors, degraded, shed]
        self._counts: List[list] = [[0] * 6]  # guarded-by: self._mu
        self._overflowed = False  # guarded-by: self._mu

    def tenant_of(self, name: str) -> str:
        """Raw prefix extraction — no bucket assignment, no bounding.
        Safe from any thread; used for event-field hints."""
        i = name.find(self.delim)
        return name if i < 0 else name[:i]

    def index_of(self, name: str, pre_split: bool = False) -> int:
        """Bucket index for a key name (or an already-extracted tenant
        id when ``pre_split``), assigning a new bucket while room
        remains and folding overflow into ``__other__``."""
        tenant = name if pre_split else self.tenant_of(name)
        with self._mu:
            i = self._idx.get(tenant)
            if i is not None:
                return i
            if len(self._tenant_names) > self.max_tenants:
                self._overflowed = True
                return 0
            i = len(self._tenant_names)
            self._idx[tenant] = i
            self._tenant_names.append(tenant)
            self._counts.append([0] * 6)
            return i

    def fold(self, tidx: np.ndarray, hits: np.ndarray,
             over: np.ndarray) -> None:
        """Vectorized bulk attribution of one drained batch: one
        bincount per column, applied to every touched bucket."""
        nb = len(self._tenant_names)  # lock-free: buckets only grow; every tidx was assigned against a ledger of <= nb buckets
        req = np.bincount(tidx, minlength=nb)
        h = np.bincount(tidx, weights=np.asarray(hits, np.float64),
                        minlength=nb)
        o = np.bincount(tidx, weights=np.asarray(over, np.float64),
                        minlength=nb)
        touched = np.nonzero(req)[0]
        with self._mu:
            for b in touched:
                c = self._counts[b]
                c[0] += int(req[b])
                c[1] += int(h[b])
                c[2] += int(o[b])

    def add(self, idx: int, field: str, n: int = 1) -> None:
        f = self.FIELDS.index(field)
        with self._mu:
            self._counts[idx][f] += int(n)

    def totals(self) -> Dict[str, int]:
        with self._mu:
            sums = [sum(c[f] for c in self._counts)
                    for f in range(6)]
        return dict(zip(self.FIELDS, sums))

    def snapshot(self) -> dict:
        with self._mu:
            tenants = {name: dict(zip(self.FIELDS, counts))
                       for name, counts in zip(self._tenant_names,
                                               self._counts)}
            overflowed = self._overflowed
        totals = {f: sum(t[f] for t in tenants.values())
                  for f in self.FIELDS}
        return {"delim": self.delim, "max_tenants": self.max_tenants,
                "overflowed": overflowed,
                "tenant_count": len(tenants),
                "tenants": tenants, "totals": totals}

    def red(self, kind: str) -> Dict[str, tuple]:
        """Cumulative (bad, total) per tenant for the SLO engine's
        per-tenant groups: ``errors`` → (errors + degraded, requests);
        ``shed`` → (shed, requests + shed)."""
        with self._mu:
            out = {}
            for name, c in zip(self._tenant_names, self._counts):
                if kind == "shed":
                    bad, total = c[5], c[0] + c[5]
                else:
                    bad, total = c[3] + c[4], c[0]
                if total:
                    out[name] = (bad, total)
            return out


class CostModel:
    """Online α-β collective cost model: T(bytes) = α + β·bytes per
    (phase, device-count) bucket, the AllReduce time model from
    "Revisiting the Time Cost Model of AllReduce" (PAPERS.md) that the
    hierarchical-reconcile ROADMAP item needs per level.

    Each ``global_fold`` / ``broadcast`` / ``peer_flush`` phase record
    contributes one (bytes, seconds) sample; the fit is closed-form
    least squares over five running sums — no history kept, no deps,
    O(1) per sample.  Thread-safe (own leaf lock).
    """

    def __init__(self):
        self._mu = threading.Lock()
        #: (phase, ndev) → [n, Σx, Σy, Σxx, Σxy]   guarded-by: self._mu
        self._b: Dict[tuple, list] = {}

    def add(self, phase: str, nbytes: int, ndev: int,
            seconds: float) -> None:
        x, y = float(nbytes), float(seconds)
        with self._mu:
            b = self._b.get((phase, int(ndev)))
            if b is None:
                b = self._b[(phase, int(ndev))] = [0, 0.0, 0.0, 0.0,
                                                   0.0]
            b[0] += 1
            b[1] += x
            b[2] += y
            b[3] += x * x
            b[4] += x * y

    @staticmethod
    def _solve(b: list) -> Optional[dict]:
        n, sx, sy, sxx, sxy = b
        if n < 2:
            return None
        det = n * sxx - sx * sx
        if det <= 1e-12 * max(n * sxx, 1.0):
            # degenerate (all samples one size): β unidentifiable,
            # report the mean as pure α
            return {"n": int(n), "alpha_s": sy / n,
                    "beta_s_per_byte": 0.0, "mean_bytes": sx / n}
        beta = (n * sxy - sx * sy) / det
        alpha = (sy - beta * sx) / n
        return {"n": int(n), "alpha_s": alpha,
                "beta_s_per_byte": beta, "mean_bytes": sx / n}

    def fit(self, phase: str, ndev: int) -> Optional[dict]:
        with self._mu:
            b = self._b.get((phase, int(ndev)))
            b = list(b) if b else None
        return self._solve(b) if b else None

    def predict(self, phase: str, ndev: int,
                nbytes: int) -> Optional[float]:
        f = self.fit(phase, ndev)
        if f is None:
            return None
        return f["alpha_s"] + f["beta_s_per_byte"] * float(nbytes)

    def snapshot(self) -> dict:
        """The ``GET /debug/costmodel`` document: every bucket's fitted
        constants (α in seconds, β in seconds/byte) + sample counts."""
        with self._mu:
            items = [(k, list(v)) for k, v in self._b.items()]
        buckets = []
        for (phase, ndev), b in sorted(items):
            f = self._solve(b)
            row = {"phase": phase, "ndev": ndev, "samples": int(b[0])}
            if f is not None:
                row.update({"alpha_us": round(f["alpha_s"] * 1e6, 3),
                            "beta_ns_per_byte":
                                round(f["beta_s_per_byte"] * 1e9, 6),
                            "mean_bytes": round(f["mean_bytes"], 1)})
            buckets.append(row)
        return {"model": "T = alpha + beta * bytes",
                "buckets": buckets}


_M64 = 0xFFFFFFFFFFFFFFFF


class _BucketTable:
    """Bounded uint64 → tenant-bucket map as three parallel columns
    sorted by key: (key u64, bucket i64, age i64).

    ONE writer (the analytics worker) merges keys in and publishes the
    result by swapping the one reference ``cols`` — never in place — so
    any thread reads a consistent triple without a lock.  ``age`` is
    when a key was last seen by a merge; over ``cap`` the keys seen
    longest ago go, by one mask over the columns, and learn again on
    their next appearance."""

    __slots__ = ("cap", "cols", "_tick")

    def __init__(self, cap: int):
        self.cap = int(cap)
        self.cols = (np.empty(0, np.uint64), np.empty(0, np.int64),
                     np.empty(0, np.int64))
        self._tick = 0

    def __len__(self) -> int:
        return self.cols[0].size

    @staticmethod
    def _find(ks: np.ndarray, keys: np.ndarray):
        """(held bool[n], pos i64[n]): ``ks[pos] == keys`` where held;
        where not, ``pos`` is where the key would be inserted."""
        if not ks.size:
            return np.zeros(len(keys), bool), np.zeros(len(keys), np.int64)
        pos = np.searchsorted(ks, keys)
        return ks[np.minimum(pos, ks.size - 1)] == keys, pos

    def known(self, keys: np.ndarray) -> np.ndarray:
        """bool[n]: which of ``keys`` the table holds."""
        return self._find(self.cols[0], keys)[0]

    def buckets(self, keys: np.ndarray) -> np.ndarray:
        """i64[n]: each key's bucket, 0 (``__other__``) where it is
        not held."""
        ks, ti, _ = self.cols
        held, pos = self._find(ks, keys)
        out = np.zeros(len(keys), np.int64)
        out[held] = ti[pos[held]]
        return out

    def get(self, key: int) -> Optional[int]:
        ks, ti, _ = self.cols
        k = np.uint64(int(key) & _M64)
        i = int(np.searchsorted(ks, k))
        if i < ks.size and ks[i] == k:
            return int(ti[i])
        return None

    def learn(self, rows: np.ndarray, bucket_of) -> tuple:
        """Merge a batch of key ``rows`` (in arrival order, some not
        held) in: the keys held are marked seen, the others inserted
        with ``bucket_of(first)`` — ``first`` being the batch row each
        new key first came in.  Returns the (rows of new keys, rows of
        new keys filed under bucket 0)."""
        order = np.argsort(rows, kind="stable")
        srt = rows[order]
        starts = np.flatnonzero(np.concatenate(
            ([True], srt[1:] != srt[:-1])))
        ends = np.append(starts[1:], srt.size)
        keys = srt[starts]  # the batch's distinct keys, sorted
        seen = self._tick + order[ends - 1]  # each key's LAST row
        self._tick += rows.size
        ks, ti, ag = self.cols
        held, pos = self._find(ks, keys)
        new = ~held
        b_new = np.asarray(bucket_of(order[starts][new]), np.int64)
        at = pos[new] + np.arange(b_new.size)
        n = ks.size + b_new.size
        old = np.ones(n, bool)
        old[at] = False
        ag = ag.copy()
        ag[pos[held]] = seen[held]
        cols = []
        for have, ins, dt in ((ks, keys[new], np.uint64),
                              (ti, b_new, np.int64),
                              (ag, seen[new], np.int64)):
            col = np.empty(n, dt)
            col[at] = ins
            col[old] = have
            cols.append(col)
        if n > self.cap:
            age = cols[2]  # distinct: exactly cap keys survive
            keep = age >= np.partition(age, n - self.cap)[n - self.cap]
            cols = [c[keep] for c in cols]
        self.cols = tuple(cols)
        per_key = (ends - starts)[new]
        return int(per_key.sum()), int(per_key[b_new == 0].sum())


class _Flush:
    """Queue sentinel: the worker sets the event when it reaches it."""

    def __init__(self):
        self.done = threading.Event()


class KeyAnalytics:
    """The analytics subsystem: tap queue + worker + sketch + phases.

    Taps copy the wave's (khash, hits, status) columns — a few KB — and
    enqueue; ``tap_reqs`` enqueues the request/response object lists
    (the worker hashes names there, recovering key names).  A full
    queue DROPS the wave and counts it: analytics must never apply
    backpressure to the serving path.
    """

    #: worker pacing: after folding a drained batch, rest this long.
    #: Everything queued in the window folds in ONE vectorized update,
    #: amortizing the per-update fixed costs — and bounding the
    #: worker's GIL duty cycle, which on small hosts otherwise convoys
    #: the serving thread's C sections.
    BATCH_INTERVAL_S = 0.1

    #: top-K gauge refresh cadence: the label-set diff walks every
    #: tracked key, so it runs on this timer (and on flush/scrape),
    #: never per fold.
    PUBLISH_INTERVAL_S = 2.0

    #: bound of the name-hash → tenant table (shed like the khash one)
    NAME_CAP = 4096

    def __init__(self, metrics=None, k: Optional[int] = None,
                 width: Optional[int] = None, queue_cap: int = 512,
                 clock=time.time):
        self.metrics = metrics
        self._clock = clock
        k = k if k is not None else _env_int("GUBER_TOPK", 256)
        width = (width if width is not None
                 else _env_int("GUBER_SKETCH_WIDTH", 4 * k))
        self._mu = threading.Lock()  # guards sketch + counters
        self.sketch = HeavyHitterSketch(k=k, width=width)  # guarded-by: self._mu
        self.phases = PhaseLedger()  # internally locked (own _mu)
        #: per-tenant RED ledger (ISSUE 11); None disables attribution
        #: entirely (the bench A/B detaches it the way it detaches
        #: the whole analytics plane)
        self._tenants: Optional[TenantLedger] = TenantLedger()
        #: α-β collective cost model; taps go straight in (leaf lock,
        #: samples arrive from reconcile/flush threads, never hot)
        self.costmodel = CostModel()
        #: khash → tenant bucket, learnt from named taps and wire
        #: learn items.  The worker thread alone writes it (one
        #: reference swap a merge); event-field hints read it.  lock-free
        self._kh_cap = max(8 * width, 4096)
        self._kh = _BucketTable(self._kh_cap)
        #: FNV-1a64 of a rate-limit NAME → tenant bucket: what lets a
        #: wire call's new khashes be learnt without reading its names
        #: (names are a handful a deployment; bounded all the same)
        self._names = _BucketTable(self.NAME_CAP)
        self._learn_kids = None  # the learn counter's label children
        self._q: "queue.Queue" = queue.Queue(maxsize=queue_cap)
        self._waves = 0  # guarded-by: self._mu
        self._dropped = 0  # guarded-by: self._mu
        self._pub_mu = threading.Lock()  # serializes gauge refreshes
        self._published: Dict[str, float] = {}  # guarded-by: self._pub_mu
        self._last_publish = 0.0  # guarded-by: self._pub_mu
        self._closing = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="key-analytics")
        self._thread.start()

    # ---- taps (serving path; must stay O(copy) and non-blocking) -------

    def tap_packed(self, khash, hits, status) -> bool:
        """Columnar wave tap: copies the three columns NOW (the caller's
        arrays may be pool-leased or shared result views) and enqueues.
        Returns False when the queue was full (wave dropped)."""
        item = ("cols",
                np.array(khash, np.uint64, copy=True),
                np.array(hits, np.int64, copy=True),
                np.array(np.asarray(status) == 1, bool),
                int(self._clock() * 1000))
        return self._put(item)

    def tap_reqs(self, reqs, resps) -> bool:
        """Object-lane tap: the worker extracts names/hits/status (and
        hashes the keys) off the serving path."""
        if not reqs:
            return True
        return self._put(("reqs", list(reqs), list(resps),
                          int(self._clock() * 1000)))

    def tap_wire_names(self, data, khash, name_hash, tlv_off, tlv_len,
                       raw: bool = False) -> bool:
        """Tenant learn tap for the columnar wire lanes, which carry
        only khashes: enqueue the (immutable) wire bytes plus the
        ingest's views — khash, the FNV-1a64 of each request's name
        (``name_hash``) and the request TLV ranges — so the WORKER can
        map new khashes to tenant ids: zero copies, zero parsing on
        the serving path.  ``raw`` marks a pre-mix khash (parse
        output); the worker applies the finalizer itself.  FIFO
        ordering guarantees the learn lands before the wave's own
        "cols"/"dev" item is folded."""
        if self._tenants is None:
            return True
        return self._put(("learn", data, khash, name_hash, tlv_off,
                          tlv_len, raw))

    def tap_flag(self, field: str, n: int = 1,
                 tenant: Optional[str] = None,
                 khash: Optional[int] = None,
                 name: Optional[str] = None) -> bool:
        """Exceptional-outcome attribution (``errors`` / ``degraded``
        / ``shed``): cheap enqueue from the serving path, resolved to
        a tenant bucket on the worker (explicit tenant id → khash
        cache → key name → ``__other__``)."""
        if self._tenants is None:
            return True
        return self._put(("flag", field, int(n), tenant, khash, name))

    def tap_cost(self, phase: str, nbytes: int, ndev: int,
                 seconds: float) -> None:
        """One collective cost sample (leaf-locked, direct — callers
        are reconcile ticks and flush completions, never wave-rate)."""
        self.costmodel.add(phase, nbytes, ndev, seconds)

    def tap_device(self, tap) -> bool:
        """Fused-engine wave tap (ISSUE 8): ``tap`` is the [4, B] int64
        device array the fused serving program emitted alongside its
        decisions — rows (khash bit-viewed, hits, over, served).  NO
        host copy happens here: the jax array is a future; the worker
        thread's np.asarray is where the device→host transfer (and any
        blocking on the wave) lands, strictly off the serving path.
        Returns False when the queue was full (wave dropped)."""
        return self._put(("dev", tap, int(self._clock() * 1000)))

    @staticmethod
    def _dev_to_cols(item):
        """Materialize a device tap on the WORKER thread → a "cols"
        item (padding / invalid / table-full rows gated out by the
        kernel-emitted ``served`` row).  None when empty or the array
        failed to materialize (a dead device must not kill the
        worker)."""
        try:
            arr = np.asarray(item[1])
            served = arr[3] != 0
            if not served.any():
                return None
            return ("cols", arr[0][served].view(np.uint64),
                    arr[1][served], arr[2][served] != 0, int(item[2]))
        except Exception:  # pragma: no cover - analytics only
            import logging

            logging.getLogger("gubernator_tpu.analytics").exception(
                "device tap materialize")
            return None

    def _put(self, item) -> bool:
        try:
            self._q.put_nowait(item)
        except queue.Full:
            with self._mu:
                self._dropped += 1
            if self.metrics is not None:
                self.metrics.analytics_dropped.inc()
            return False
        return True

    # ---- phase attribution ---------------------------------------------

    def observe_phase(self, phase: str, seconds: float,
                      cpu: Optional[float] = None, exemplar=None) -> None:
        """One phase sample → histogram + /debug/phases ledger (the
        sink ``tracing.phase`` hands its samples to).  ``cpu``: the
        thread's CPU seconds over the same section, for the phases
        that record them.  ``exemplar`` (ISSUE 12): a recent sampled
        trace's label dict, attached to the histogram observation so a
        slow-phase bucket links to one concrete trace (openmetrics
        exposition)."""
        if seconds < 0.0:
            seconds = 0.0
        self.phases.observe(phase, seconds)
        if self.metrics is not None:
            self.metrics.observe_phase(phase, seconds, cpu, exemplar)

    # ---- worker ---------------------------------------------------------

    def _run(self) -> None:
        q = self._q
        while True:
            item = q.get()
            cols: list = []
            learns: list = []
            while True:
                if item is None:
                    self._fold_window(cols, learns)
                    return
                if isinstance(item, _Flush):
                    self._fold_window(cols, learns)
                    cols, learns = [], []
                    item.done.set()
                elif item[0] == "cols":
                    cols.append(item)
                elif item[0] == "dev":
                    # fused-engine device tap: the device→host copy
                    # happens HERE, on the worker
                    c = self._dev_to_cols(item)
                    if c is not None:
                        cols.append(c)
                elif item[0] == "learn":
                    # a tenant learn MUST land before the fold of any
                    # cols queued behind it (FIFO), and folding the
                    # ones queued AHEAD of it later is harmless — so
                    # the window's learns merge in ONE pass, right
                    # before whatever next reads the khash table
                    learns.append(item)
                elif item[0] == "flag":
                    self._safe_learn(learns)
                    learns = []
                    self._safe_flag(item)
                else:
                    # object-lane (named) tap: fold queued columns
                    # first so wave order is preserved
                    self._fold_window(cols, learns)
                    cols, learns = [], []
                    self._safe_apply(item)
                try:
                    item = q.get_nowait()
                except queue.Empty:
                    break
            self._fold_window(cols, learns)
            if not self._closing:
                time.sleep(self.BATCH_INTERVAL_S)

    def _fold_window(self, cols: list, learns: list) -> None:
        self._safe_learn(learns)
        self._fold_cols(cols)

    def _fold_cols(self, cols: list) -> None:
        """Everything the drain window collected folds in ONE sketch
        update (one unique/sort/admission pass for the whole burst)."""
        if not cols:
            return
        try:
            if len(cols) == 1:
                _, khash, hits, over, t_ms = cols[0]
            else:
                khash = np.concatenate([c[1] for c in cols])
                hits = np.concatenate([c[2] for c in cols])
                over = np.concatenate([c[3] for c in cols])
                t_ms = cols[-1][4]
            with self._mu:
                self.sketch.update(khash, hits, over, t_ms)
                self._waves += len(cols)
            if self._tenants is not None:
                self._fold_tenants(np.asarray(khash, np.uint64),
                                   np.asarray(hits, np.int64),
                                   np.asarray(over, bool))
            if self.metrics is not None:
                self.metrics.analytics_waves.inc(len(cols))
            self._maybe_publish()
        except Exception:  # pragma: no cover - must never die
            import logging

            logging.getLogger("gubernator_tpu.analytics").exception(
                "analytics fold")

    def _safe_apply(self, item) -> None:
        try:
            self._apply(item)
        except Exception:  # pragma: no cover - must never die
            import logging

            logging.getLogger("gubernator_tpu.analytics").exception(
                "analytics tap apply")

    def _apply(self, item) -> None:
        _, reqs, resps, t_ms = item
        from .hashing import hash_request_keys

        khash = hash_request_keys([r.name for r in reqs],
                                  [r.unique_key for r in reqs])
        hits = np.fromiter((int(r.hits) for r in reqs), np.int64,
                           len(reqs))
        over = np.fromiter((int(r.status) == 1 for r in resps),
                           bool, len(resps))
        names = [f"{r.name}_{r.unique_key}" for r in reqs]
        with self._mu:
            self.sketch.update(khash, hits, over, t_ms, names=names)
            self._waves += 1
        tl = self._tenants
        if tl is not None:
            # learn khash → tenant (object lanes carry names), then
            # attribute through the same fold path as the wire lanes
            tidx = np.fromiter(
                (tl.index_of(r.name) for r in reqs), np.int64,
                len(reqs))
            if not self._kh.known(khash).all():
                self._kh.learn(khash, lambda first: tidx[first])
            tl.fold(tidx, hits, over)
            for i, r in enumerate(resps):
                if getattr(r, "error", ""):
                    tl.add(int(tidx[i]), "errors", 1)
        if self.metrics is not None:
            self.metrics.analytics_waves.inc()
        self._maybe_publish()

    # ---- tenant attribution (worker thread) -----------------------------

    def _fold_tenants(self, khash, hits, over) -> None:
        """Attribute one folded batch to tenant buckets: vectorized
        searchsorted against the learned khash table; khashes it
        can't resolve land in ``__other__`` (bucket 0) so every row is
        counted exactly once."""
        self._tenants.fold(self._kh.buckets(khash), hits, over)

    def _safe_learn(self, items) -> None:
        if not items or self._tenants is None:
            return
        try:
            with phase("analytics.learn", self, cpu=True):
                self._learn(items)
        except Exception:  # pragma: no cover - must never die
            import logging

            logging.getLogger("gubernator_tpu.analytics").exception(
                "tenant learn")

    def _learn(self, items) -> None:
        """Merge one drain window's learn items into the khash →
        bucket table: numpy over the rows, Python only for each
        rate-limit NAME never seen before (one request TLV decoded for
        it).  A window whose khashes are all held costs one
        ``searchsorted``."""
        khs, nhs = [], []
        for _, _data, kh, nh, _off, _len, raw in items:
            kh = np.asarray(kh, np.uint64)
            if raw:
                kh = mix64_np(kh)
                kh[kh == 0] = 1  # as the ingest files the row
            khs.append(kh)
            nhs.append(nh)
        one = len(items) == 1
        kh = khs[0] if one else np.concatenate(khs)
        n_new = n_other = 0
        if not self._kh.known(kh).all():
            nh = np.asarray(nhs[0] if one else np.concatenate(nhs),
                            np.uint64)
            n_new, n_other = self._kh.learn(
                kh, lambda rows: self._name_buckets(nh[rows], rows,
                                                    items))
        if self.metrics is not None:
            kids = self._learn_kids
            if kids is None:
                c = self.metrics.analytics_learn_rows
                kids = self._learn_kids = tuple(
                    c.labels(outcome=o)
                    for o in ("known", "learned", "other"))
            kids[0].inc(kh.size - n_new)
            kids[1].inc(n_new - n_other)
            kids[2].inc(n_other)

    def _name_buckets(self, nh: np.ndarray, rows: np.ndarray,
                      items) -> np.ndarray:
        """Tenant bucket of each name hash in ``nh`` (``rows``: the
        window row each came from).  A name hash the name table does
        not hold is read ONCE, from the request TLV of the first of
        those rows, and its tenant gets a bucket (``__other__`` when
        the ledger is full)."""
        names = self._names
        if not names.known(nh).all():
            starts = np.cumsum([0] + [len(it[2]) for it in items])
            by_row = np.argsort(rows)

            def read(first):
                # in the order the window's rows came: the ledger
                # hands out its buckets first come, first served
                out = np.zeros(first.size, np.int64)
                for j in np.argsort(first).tolist():
                    row = int(rows[by_row[first[j]]])
                    it = int(np.searchsorted(starts, row, "right")) - 1
                    _, data, _kh, _nh, off, ln, _raw = items[it]
                    lo = int(off[row - starts[it]])
                    hi = lo + int(ln[row - starts[it]])
                    pairs = iter_wire_names(memoryview(data)[lo:hi])
                    if pairs:
                        out[j] = self._tenants.index_of(pairs[0][0])
                return out

            names.learn(nh[by_row], read)
        return names.buckets(nh)

    def _safe_flag(self, item) -> None:
        try:
            tl = self._tenants
            if tl is None:
                return
            _, field, n, tenant, khash, name = item
            idx = None
            if tenant is not None:
                idx = tl.index_of(tenant, pre_split=True)
            elif khash is not None:
                idx = self._kh.get(khash)
            if idx is None:
                idx = tl.index_of(name) if name is not None else 0
            tl.add(idx, field, n)
        except Exception:  # pragma: no cover - must never die
            import logging

            logging.getLogger("gubernator_tpu.analytics").exception(
                "tenant flag")

    def tenant_hint(self, khash: Optional[int] = None,
                    name: Optional[str] = None) -> Optional[str]:
        """Best-effort tenant id for event fields: khash → learned
        bucket name (a lock-free read of the columns the worker last
        published), else the raw prefix of ``name``.  Never assigns
        buckets, so it is safe (and cheap) from any serving thread."""
        tl = self._tenants
        if tl is None:
            return None
        if khash is not None:
            idx = self._kh.get(khash)
            if idx is not None:
                try:
                    return tl._tenant_names[idx]
                except IndexError:  # pragma: no cover - benign race
                    return None
        if name is not None:
            return tl.tenant_of(name)
        return None

    def _maybe_publish(self) -> None:
        now = time.monotonic()
        with self._pub_mu:
            # check-then-set under the lock: the scrape thread's
            # republish() writes the same stamp (guarded-by sweep found
            # this as a racy double-publish window)
            due = now - self._last_publish >= self.PUBLISH_INTERVAL_S
            if due:
                self._last_publish = now
        if due:
            self._publish()

    def republish(self) -> None:
        """Scrape-time gauge refresh (daemon /metrics handler): the
        label churn costs the scraper, never the analytics worker."""
        with self._pub_mu:
            self._last_publish = time.monotonic()
        self._publish()

    def _publish(self) -> None:
        """Refresh gubernator_topkey_overlimit_total for the CURRENT
        top-K only: labels of departed keys are removed first, so the
        family's cardinality is bounded by K at every scrape — never
        per-key labels over the whole key space."""
        if self.metrics is None:
            return
        with self._mu:
            top = self.sketch.topk()
        fresh = {}
        for e in top:
            label = e["key"] or f"0x{e['khash']:016x}"
            fresh[label] = float(e["over_limit"])
        gauge = self.metrics.topkey_overlimit
        with self._pub_mu:
            for label in list(self._published):
                if label not in fresh:
                    try:
                        gauge.remove(label)
                    except KeyError:  # pragma: no cover - already gone
                        pass
            for label, val in fresh.items():
                gauge.labels(key=label).set(val)
            self._published = fresh
        self._publish_tenants()

    def _publish_tenants(self) -> None:
        """gubernator_tenant_* gauge refresh: cardinality is bounded
        by the ledger itself (GUBER_TENANT_MAX + __other__), and
        buckets never depart, so no label removal pass is needed."""
        tl = self._tenants
        m = self.metrics
        if tl is None or m is None:
            return
        gauges = (m.tenant_requests, m.tenant_hits,
                  m.tenant_over_limit, m.tenant_errors,
                  m.tenant_degraded, m.tenant_shed)
        snap = tl.snapshot()
        for tenant, counts in snap["tenants"].items():
            for gauge, field in zip(gauges, TenantLedger.FIELDS):
                gauge.labels(tenant=tenant).set(float(counts[field]))

    # ---- reporting ------------------------------------------------------

    def flush(self, timeout: float = 10.0) -> bool:
        """Block until every tap enqueued so far has been applied (and
        the gauge republished) — tests and snapshot callers."""
        f = _Flush()
        try:
            self._q.put(f, timeout=timeout)
        except queue.Full:
            return False
        ok = f.done.wait(timeout)
        if ok:
            self._publish()
        return ok

    def sketch_count(self, khash: int) -> int:
        """Thread-safe tracked-count read for one key hash (0 when
        untracked) — the mesh tier's overflow rank (instance.py ›
        _mesh_overflow_victim)."""
        with self._mu:
            return self.sketch.count_of(khash)

    def sketch_counts(self, khashes) -> np.ndarray:
        """Batched :meth:`sketch_count`, i64[n] — ONE lock acquisition
        and one vectorised probe."""
        with self._mu:
            return self.sketch.counts_of(khashes)

    def sketch_known(self, khashes) -> np.ndarray:
        """Thread-safe :meth:`HeavyHitterSketch.known_of`, i64[n]: the
        tiered store's admission and victim rank — ONE lock acquisition
        and one vectorised probe for a wave's served cold keys or a
        migration pass's victim candidates (tiering.py › _admit,
        › _pick_victims)."""
        with self._mu:
            return self.sketch.known_of(khashes)

    def stats(self) -> dict:
        with self._mu:
            return {"k": self.sketch.k, "width": self.sketch.width,
                    "waves_tapped": self._waves,
                    "taps_dropped": self._dropped,
                    "tracked_keys": len(self.sketch),
                    "queue_depth": self._q.qsize()}

    def mem_stats(self) -> dict:
        """Memory-ledger probe feed (ISSUE 13): the sketch's host
        bytes are its five width-length columns, live at all times."""
        with self._mu:
            sk = self.sketch
            nbytes = int(sk._cnt.nbytes + sk._err.nbytes
                         + sk._over.nbytes + sk._last.nbytes
                         + sk._kh.nbytes)
            return {"bytes": nbytes, "width": sk.width,
                    "used": len(sk),
                    "total_weight": int(sk.total_weight)}

    def rank_distribution(self, limit: int = 4096) -> List[int]:
        """Space-Saving rank distribution: tracked counts, descending —
        the hot table's marginal-hit-density curve for the memory
        ledger's advisor (memledger.py › advise).  Rank r's count is
        the observed demand a cache of r+1 rows would capture at the
        margin; the advisor extrapolates past ``limit``."""
        with self._mu:
            used = len(self.sketch)
            cnt = np.sort(self.sketch._cnt[:used])[::-1]
        return [int(v) for v in cnt[:max(int(limit), 1)]]

    def topkeys_snapshot(self, limit: Optional[int] = None) -> dict:
        """The ``GET /debug/topkeys`` document (owner resolution is the
        daemon's job — it knows the ring)."""
        with self._mu:
            top = self.sketch.topk(limit)
            bound = self.sketch.error_bound()
            total = self.sketch.total_weight
        out = self.stats()
        out.update({"total_hits_observed": total,
                    "admission_error_bound": bound,
                    "keys": [dict(e, khash=f"0x{e['khash']:016x}")
                             for e in top]})
        return out

    def phases_snapshot(self) -> dict:
        return {"phases": self.phases.snapshot()}

    def tenants_snapshot(self) -> dict:
        """The ``GET /debug/tenants`` document."""
        tl = self._tenants
        if tl is None:
            return {"enabled": False}
        out = tl.snapshot()
        out["enabled"] = True
        return out

    def tenant_red(self, kind: str) -> Dict[str, tuple]:
        """Per-tenant cumulative (bad, total) feed for the SLO
        engine's tenant groups (empty when attribution is off)."""
        tl = self._tenants
        return tl.red(kind) if tl is not None else {}

    def tenant_totals(self) -> Dict[str, int]:
        tl = self._tenants
        if tl is None:
            return {}
        return tl.totals()

    def costmodel_snapshot(self) -> dict:
        """The ``GET /debug/costmodel`` document."""
        return self.costmodel.snapshot()

    def close(self) -> None:
        self._closing = True
        try:
            self._q.put_nowait(None)
        except queue.Full:  # drain enough to deliver the poison pill
            try:
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._q.put(None)
        self._thread.join(timeout=5)
