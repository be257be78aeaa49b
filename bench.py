"""Benchmark: rate-limit decisions/sec/chip on the north-star workload.

Workload (BASELINE.json › north_star): TOKEN_BUCKET, 10M distinct keys
drawn Zipf(1.1), hits=1, limit=100, duration=10s — the reference's
`gubernator-cli` load shape at the 10M-key working set (client batches
of 1000).  The dispatcher coalesces client batches into one device batch
per step; each step is one jit program — probe → gather → branchless
update → scatter.  TWO table-update modes are measured and the faster
one is the headline (extra.step_mode records which):

- "copy": no donation; scatters fuse into a dense streaming copy of the
  table (~2 × CAP × row-bytes per launch).
- "donate": table aliases in/out; cond-gated cold columns pass through
  copy-free and hot scatters update in place where the lowering allows
  (core/step.py › decide_batch_donated) — per-step traffic ~B-sized.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}
vs_baseline is relative to the 50M decisions/s/chip north-star target
(BASELINE.json records no published reference numbers).
"""
import json
import os
import sys
import time

import numpy as np

# Persistent compile cache: the decision-step program is large (a
# cold TPU compile is minutes); cache across bench invocations
# (gubernator_tpu.compilecache owns the dir choice).
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from gubernator_tpu import compilecache

compilecache.setup()


def log(*a):
    print(*a, file=sys.stderr, flush=True)


FAST = bool(os.environ.get("GUBER_BENCH_FAST"))
#: north star is 10M keys; CAP 2^26 (load 0.149) + the default 8-probe
#: window is the zero-loss flagship shape: the EXACT 10M-key populate
#: inserts every key (0 errs, tools/populate_errs_check.py; CAP
#: 2^25/8-probe loses 71 keys and CAP 2^24/8-probe lost 17,739).
#: GUBER_BENCH_FAST shrinks the workload — its config string says so;
#: it never stands in for the 10M-key number.
N_KEYS = int(os.environ.get("GUBER_BENCH_KEYS",
                            1_000_000 if FAST else 10_000_000))
CAP = int(os.environ.get("GUBER_BENCH_CAP", 1 << 21 if FAST else 1 << 26))
#: the probe window stays at the serving default (core/step.py ›
#: PROBES, a constant of the table) everywhere.
#: device batch = coalesced client batches of 1024 (GUBER_BENCH_B
#: overrides for batch-size sweeps)
B = int(os.environ.get("GUBER_BENCH_B", 8192 if FAST else 65536))
ZIPF_A = 1.1
LIMIT = 100
DURATION_MS = 10_000
NOW0 = 1_760_000_000_000
TARGET = 50e6


def _host_cores() -> int:
    """Schedulable cores for THIS process (affinity-aware where the
    platform supports it)."""
    return (len(os.sched_getaffinity(0))
            if hasattr(os, "sched_getaffinity")
            else (os.cpu_count() or 1))


def _keyhash(x: np.ndarray) -> np.ndarray:
    """Key-id → 64-bit hash (stand-in for host string hashing, which is
    not what this benchmark measures — see extra.host_hash_mkeys)."""
    from gubernator_tpu.hashing import mix64_np

    x = mix64_np((x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64))
    return np.where(x == 0, np.uint64(1), x)


def pad_chunk(chunk: np.ndarray, size: int) -> np.ndarray:
    """Pad a trailing populate chunk to the device batch size by
    repeating its last id."""
    if len(chunk) < size:
        chunk = np.concatenate(
            [chunk, np.full(size - len(chunk), chunk[-1], np.uint64)])
    return chunk


def main() -> int:
    # The one section that starts processes (the SO_REUSEPORT group's
    # CPU-pinned ingest workers) runs FIRST, before this process has
    # touched JAX: from the first backend call on, the bench starts no
    # process.  Its workers serve the XLA step like every other
    # XLA-labeled row (see run_secondary_configs).
    os.environ["GUBER_STEP_IMPL"] = "xla"
    group_rows = _run_section("group")

    import jax
    import jax.numpy as jnp

    from gubernator_tpu.core.batch import RequestBatch
    from gubernator_tpu.core.step import (PROBES, decide_batch,
                                          decide_batch_donated)
    from gubernator_tpu.core.table import init_table

    backend = jax.default_backend()
    device = _device_row()
    log(f"backend={backend} devices={jax.devices()}")

    rng = np.random.default_rng(42)
    n_batches = 8
    draws = rng.zipf(ZIPF_A, size=n_batches * B) % N_KEYS
    key_batches = [jnp.asarray(_keyhash(draws[i * B:(i + 1) * B].astype(np.uint64)))
                   for i in range(n_batches)]

    i64 = jnp.int64
    const = dict(
        hits=jnp.ones(B, i64),
        limit=jnp.full(B, LIMIT, i64),
        duration=jnp.full(B, DURATION_MS, i64),
        eff_ms=jnp.full(B, DURATION_MS, i64),
        greg_end=jnp.zeros(B, i64),
        behavior=jnp.zeros(B, jnp.int32),
        algorithm=jnp.zeros(B, jnp.int32),
        burst=jnp.full(B, LIMIT, i64),
        valid=jnp.ones(B, bool),
    )

    def make_batch(keys):
        return RequestBatch(key=keys, **const)

    # Hot-loop time source: ONE host→device transfer, then a jitted
    # device-side bump per step.  A per-rep `jnp.asarray(now0 + r)` is a
    # host→device transfer inside the timed loop; the device bump keeps
    # the loop transfer-free with identical time semantics (now
    # advances by 1 per step).
    _bump1 = _bump_fn()
    _bump1(jnp.asarray(0, i64)).block_until_ready()  # compile now, not
    # inside any timed region below

    populate_errs = {}

    def populate(step_fn, st, label):
        """Insert ALL N_KEYS distinct keys so the measured loop runs at
        the claimed working set (load factor N_KEYS/CAP), not at the few
        hundred thousand distinct keys a handful of Zipf draws covers —
        the sustained number must be the steady-state resident-table
        rate it claims to be.  Insert failures are COUNTED and reported
        (extra.populate_errs): the flagship claim is that the shape
        serves 100% of its working set, and a key that lost every claim
        round errs on every future request."""
        ids = np.arange(N_KEYS, dtype=np.uint64)
        now_pop = jnp.asarray(NOW0, i64)
        errs = 0
        for a in range(0, N_KEYS, B):
            chunk = pad_chunk(ids[a:a + B], B)
            st, out = step_fn(st, make_batch(jnp.asarray(_keyhash(chunk))),
                              now_pop)
            errs += int(np.asarray(out.err).sum())
        out.status.block_until_ready()
        populate_errs[label] = errs
        if errs:
            log(f"[{label}] WARNING: {errs} keys failed to insert "
                f"during populate — the rate below does not serve "
                f"100% of the working set")
        return st

    def measure_mode(step_fn, label, sustain_target=15_000_000,
                     init_fn=init_table):
        """Compile, populate the full working set, then time a sustained
        dispatch loop at steady state."""
        st = init_fn(CAP)
        t0 = time.perf_counter()
        st, out = step_fn(st, make_batch(key_batches[0]),
                          jnp.asarray(NOW0, i64))
        out.status.block_until_ready()
        log(f"[{label}] compile+first step in "
            f"{time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        st = populate(step_fn, st, label)
        log(f"[{label}] populated {N_KEYS} keys "
            f"(load {N_KEYS/CAP:.2f}) in {time.perf_counter() - t0:.1f}s")
        now_dev = jnp.asarray(NOW0, i64)
        for i in range(1, n_batches):
            now_dev = _bump1(now_dev)
            st, out = step_fn(st, make_batch(key_batches[i]), now_dev)
        out.status.block_until_ready()
        reps = max(1, int(sustain_target / B / n_batches)) * n_batches
        now_dev = jnp.asarray(NOW0 + 100, i64)
        t0 = time.perf_counter()
        for r in range(reps):
            st, out = step_fn(st, make_batch(key_batches[r % n_batches]),
                              now_dev)
            now_dev = _bump1(now_dev)
        out.status.block_until_ready()
        dt = time.perf_counter() - t0
        rate = reps * B / dt
        log(f"[{label}] sustained: {reps * B} decisions in {dt:.3f}s "
            f"→ {rate/1e6:.2f}M/s")
        return rate, st

    # mode 1: dense-copy step (safe everywhere)
    dps_copy, state = measure_mode(decide_batch, "copy")
    # mode 2: donated step — in-place updates where the lowering allows;
    # this is the mode that breaks the CAP-linear streaming wall
    try:
        dps_donate, _ = measure_mode(decide_batch_donated, "donate")
    except Exception as e:  # noqa: BLE001
        dps_donate = 0.0
        _FAILED_SECTIONS.append("donate")
        log(f"donated-step mode failed: {e!r:.200}")
    # mode 3: hand Pallas kernel (ops/pallas_step.py) — its rate is a
    # FLOOR independent of XLA's scatter/gather lowering choices (the
    # 209 ms/step copy-mode episode).  Device backends only: interpret
    # mode is a python-level emulator, minutes per batch.  The bucket
    # table gets 2× the capacity (its own layout, its own budget — a
    # sizing the benchmark inherited from the kernel's former 8-slot
    # buckets) and a measured err fraction gates the duel: a rate that
    # isn't serving the whole working set must not win the headline.
    dps_pallas, pallas_err_frac = 0.0, None
    #: the kernel's bucketized table gets 2× the XLA CAP (one sizing
    #: policy — the reporting fields below must reference THIS variable)
    pallas_rows = min(CAP * 2, 1 << 26)
    if backend != "cpu" and not os.environ.get("GUBER_BENCH_NO_PALLAS"):
        st_p = st_p2 = sample = None
        try:
            from gubernator_tpu.ops.pallas_step import (
                decide_batch_pallas, init_pallas_table)

            dps_pallas, st_p = measure_mode(
                decide_batch_pallas, "pallas",
                sustain_target=4_000_000,
                init_fn=lambda cap: init_pallas_table(pallas_rows))
            st_p2, sample = decide_batch_pallas(
                st_p, make_batch(key_batches[0]),
                jnp.asarray(NOW0 + 10_000, i64))
            pallas_err_frac = round(
                float(np.asarray(sample.err).mean()), 6)
            log(f"[pallas] err fraction at steady state: "
                f"{pallas_err_frac}")
        except Exception as e:  # noqa: BLE001
            _FAILED_SECTIONS.append("pallas_step")
            log(f"pallas-step mode failed: {e!r:.300}")
        finally:
            # drop the kernel's device buffers NOW, on every path (the
            # GBs of bucket table + outputs), before the sections build
            # their own tables in this process
            del st_p, st_p2, sample
    rates = {"copy": dps_copy, "donate": dps_donate,
             "pallas": dps_pallas}
    eligible = dict(rates)
    if pallas_err_frac is None or pallas_err_frac > 0.005:
        # bucket-overflow err rows aren't served decisions: a rate
        # that drops part of the working set can't win the headline
        eligible.pop("pallas")
        if pallas_err_frac:
            log(f"[pallas] disqualified from winning the duel: "
                f"err fraction {pallas_err_frac} > 0.005")
    step_mode = max(eligible, key=eligible.get)
    dps = eligible[step_mode]
    # sections serve through the engines, which run the XLA step — keep
    # their mode the best XLA lowering even if pallas wins the duel
    xla_mode = "donate" if dps_donate > dps_copy else "copy"
    step_best = (decide_batch_donated if xla_mode == "donate"
                 else decide_batch)
    log(f"headline mode: {step_mode} ({dps/1e6:.2f}M/s); "
        f"xla mode for sections: {xla_mode}")

    # Checkpoint the headline IMMEDIATELY: every section below needs
    # its own cold compile — the measured record is on disk before a
    # later stage can fail.
    result = {
        "metric": (f"rate-limit decisions/sec/chip @{N_KEYS//1_000_000}M-key"
                   f" Zipf({ZIPF_A})"),
        "value": round(dps),
        "unit": "decisions/s",
        "vs_baseline": round(dps / TARGET, 4),
        "extra": {
            "step_mode": step_mode,
            "copy_mode_decisions_per_s": round(dps_copy),
            "donate_mode_decisions_per_s": round(dps_donate),
            "pallas_mode_decisions_per_s": round(dps_pallas),
            "pallas_err_fraction": pallas_err_frac,
            # the kernel owns its table layout: bucketized AoS rows,
            # sized independently of the XLA CAP in `config` — the
            # headline must not be attributed to a table it didn't use
            "pallas_table_rows": (pallas_rows
                                  if pallas_err_frac is not None
                                  else None),
            "device_batch": B,
            "backend": backend,
            "device": device,
            "populate_errs": dict(populate_errs),
            "probes": PROBES,
            "ksplit": int(os.environ.get("GUBER_KSPLIT", "0")),
            "config": (f"TOKEN_BUCKET {N_KEYS} keys Zipf({ZIPF_A}) hits=1 "
                       f"CAP={CAP} "
                       f"probes={PROBES}"),
            "baseline_is": ("north-star target 50M decisions/s/chip (no "
                            "published reference numbers; BASELINE.md)"),
            "baseline_configs": {},
        },
    }
    if step_mode == "pallas":
        result["extra"]["config"] += (
            f" (headline mode pallas: bucketized table "
            f"{pallas_rows} rows, not CAP)")
    _write_partial(result)

    # dispatch round-trip floor: a trivial op's dispatch→sync time.
    # The client-batch percentiles below include this floor, so
    # recording it lets the p99<2ms target be decomposed into
    # device+host work vs dispatch cost from this JSON alone.
    link_p50 = link_p99 = -1.0
    try:
        one = jnp.ones((), jnp.int32)
        trivial = jax.jit(lambda x: x + 1)
        trivial(one).block_until_ready()
        link = []
        for _ in range(60):
            t0 = time.perf_counter()
            trivial(one).block_until_ready()
            link.append((time.perf_counter() - t0) * 1e3)
        link_p50 = float(np.percentile(link, 50))
        link_p99 = float(np.percentile(link, 99))
        log(f"dispatch round-trip: p50={link_p50:.3f}ms "
            f"p99={link_p99:.3f}ms")
    except Exception as e:  # noqa: BLE001
        _FAILED_SECTIONS.append("dispatch_roundtrip")
        log(f"dispatch round-trip probe failed: {e!r:.200}")

    # single-batch round-trip latency (host dispatch included), in the
    # winning mode — the copy cost it avoids is latency too
    p50 = p99 = -1.0
    out = None
    try:
        lats = []
        for i in range(50):
            t0 = time.perf_counter()
            state, out = step_best(state,
                                   make_batch(key_batches[i % n_batches]),
                                   jnp.asarray(NOW0 + 500 + i, i64))
            out.status.block_until_ready()
            lats.append((time.perf_counter() - t0) * 1e3)
        p50 = float(np.percentile(lats, 50))
        p99 = float(np.percentile(lats, 99))
        log(f"latency: p50={p50:.3f}ms p99={p99:.3f}ms (batch={B})")
    except Exception as e:  # noqa: BLE001
        _FAILED_SECTIONS.append("latency")
        log(f"latency section failed: {e!r:.200}")

    # Everything below is a section: each runs inline, in this process
    # — the one that holds the chip.  Drop the headline's tables first
    # (closures pin the arrays through their cells) so the sections'
    # own tables fit.
    del state, out, key_batches, const, make_batch, populate
    del measure_mode, step_best

    # device-resident superstep (fresh compile)
    scan_rows = _run_section("scan")
    dps_scan = float(scan_rows.get("device_scan_decisions_per_s", 0.0))
    if "error" in scan_rows:
        log(f"device-scan section: {scan_rows['error']}")
    else:
        log(f"device-scan sustained: {dps_scan/1e6:.2f}M/s "
            f"(R={scan_rows.get('scan_R')})")

    # client-shaped latency: one max-size GetRateLimits batch (1000 reqs
    # in a 1024 bucket) per device call — the p99<2ms target's shape.
    os.environ["GUBER_BENCH_STEP_MODE"] = xla_mode
    lat_rows = _run_section("lat_client")
    p50_c = float(lat_rows.get("client_batch_p50_ms", -1.0))
    p99_c = float(lat_rows.get("client_batch_p99_ms", -1.0))
    if "error" in lat_rows:
        log(f"client-batch latency section: {lat_rows['error']}")
    else:
        log(f"client-batch latency: p50={p50_c:.3f}ms p99={p99_c:.3f}ms "
            f"(batch=1024)")

    # host-side string-hash throughput (the other half of a real dispatch)
    from gubernator_tpu.hashing import hash_keys
    names = [f"bench_k{i}" for i in range(100_000)]
    t0 = time.perf_counter()
    hash_keys(names)
    hash_mkeys = len(names) / (time.perf_counter() - t0) / 1e6

    result["extra"].update({
        "device_scan_decisions_per_s": round(dps_scan),
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "client_batch_p50_ms": round(p50_c, 3),
        "client_batch_p99_ms": round(p99_c, 3),
        "link_roundtrip_p50_ms": round(link_p50, 3),
        "link_roundtrip_p99_ms": round(link_p99, 3),
        "host_hash_mkeys_per_s": round(hash_mkeys, 2),
    })
    # a consumer of this JSON must be able to tell a failed section
    # (sentinel 0 / -1 values) from a measured one
    if "error" in scan_rows:
        result["extra"]["device_scan_error"] = scan_rows["error"]
    if "error" in lat_rows:
        result["extra"]["client_batch_error"] = lat_rows["error"]
    # Checkpoint again after the latency sections and after every
    # secondary config: a late-stage failure must not cost the rows
    # already measured.
    _write_partial(result)

    def ck(cfgs):
        result["extra"]["baseline_configs"] = cfgs
        _write_partial(result)

    configs = run_secondary_configs(xla_mode, checkpoint=ck,
                                    done={"group": group_rows})
    result["extra"]["baseline_configs"] = configs
    # provenance: was the tree guberlint-clean when this row was
    # measured?  A BENCH row from an unanalyzable tree (violated lock
    # discipline, drifted registries) is a number with an asterisk —
    # record the asterisk (CONCURRENCY.md; tools/guberlint).
    result["extra"]["lint_clean"] = _lint_clean()
    _write_partial(result)
    print(json.dumps(result))
    if _FAILED_SECTIONS:
        log(f"FAILED sections: {', '.join(_FAILED_SECTIONS)}")
        return 1
    return 0


def _device_row() -> dict:
    """The device every number of this process was measured on, as JAX
    reports it — every row carries it."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _lint_clean():
    """Provenance block: the guberlint verdict for the tree this row
    was measured on (clean flag + pass/violation counts) plus the
    process's compile-ledger verdict — the runtime retrace
    cross-check.  None when the linter itself could not run (never
    fails the bench)."""
    try:
        from tools.guberlint import PASS_NAMES, run_passes

        violations = run_passes()
        block = {"clean": not violations, "passes": len(PASS_NAMES),
                 "violations": len(violations)}
    except Exception as e:  # noqa: BLE001 - provenance only
        log(f"lint_clean probe failed: {(str(e) or repr(e))[:120]}")
        return None
    try:
        from gubernator_tpu.compileledger import LEDGER

        block["compile_ledger"] = LEDGER.verdict()
    except Exception as e:  # noqa: BLE001 - provenance only
        log(f"compile_ledger probe failed: {(str(e) or repr(e))[:120]}")
        block["compile_ledger"] = None
    return block


PARTIAL_PATH = os.environ.get("GUBER_BENCH_PARTIAL",
                              "/tmp/gubernator_bench_partial.json")


def _write_partial(result: dict) -> None:
    try:
        tmp = PARTIAL_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f)
        os.replace(tmp, PARTIAL_PATH)
    except OSError as e:  # pragma: no cover - diagnostics only
        log(f"partial checkpoint write failed: {e}")


_BUMP_CACHE: dict = {}


def _bump_fn(delta=1):
    """Shared jitted device-side `now += delta` (one compile per delta
    per process — jit caches per function object, so per-call lambdas
    would re-trace every time)."""
    f = _BUMP_CACHE.get(delta)
    if f is None:
        import jax

        f = jax.jit(lambda t: t + delta)
        _BUMP_CACHE[delta] = f
    return f


def _sustain(decide_batch, jnp, state, batches, reps, now0):
    """Measure a sustained dispatch loop → decisions/s.  The advancing
    `now` lives on device (one transfer + a jitted bump per rep), so
    the timed loop carries no host→device transfers."""
    i64 = jnp.int64
    bump = _bump_fn()
    # warm the bump OUTSIDE the timed region (its first call
    # compiles): now0-1 → now0
    now_dev = bump(jnp.asarray(now0 - 1, i64))
    now_dev.block_until_ready()
    out = None
    t0 = time.perf_counter()
    for r in range(reps):
        state, out = decide_batch(state, batches[r % len(batches)],
                                  now_dev)
        now_dev = bump(now_dev)
    out.status.block_until_ready()
    dt = time.perf_counter() - t0
    return reps * batches[0].key.shape[0] / dt, state


# ---- sections -----------------------------------------------------------
#
# Every secondary config (and the client-batch latency probe) is a
# SECTION: a self-contained function that builds its own inputs, runs,
# and returns a dict of result rows.  Sections run INLINE, in the one
# process that holds the chip (a chip belongs to one process; the
# bench starts none after it has touched JAX).  A single section runs
# alone via GUBER_BENCH_SECTION=<name> python bench.py.  A section
# that raises is recorded as an error row AND makes the process exit
# nonzero.


def _mk_batch(jnp, keys, **over):
    """RequestBatch with bench-default columns (scalar-now serving
    shape: the `now` column is 0 so _sustain's advancing scalar now
    drives time)."""
    from gubernator_tpu.core.batch import RequestBatch

    i64, i32 = jnp.int64, jnp.int32
    B2 = keys.shape[0]
    cols = dict(
        hits=jnp.ones(B2, i64), limit=jnp.full(B2, LIMIT, i64),
        duration=jnp.full(B2, DURATION_MS, i64),
        eff_ms=jnp.full(B2, DURATION_MS, i64),
        greg_end=jnp.zeros(B2, i64), behavior=jnp.zeros(B2, i32),
        algorithm=jnp.zeros(B2, i32), burst=jnp.full(B2, LIMIT, i64),
        valid=jnp.ones(B2, bool),
        now=jnp.zeros(B2, i64))
    cols.update(over)
    return RequestBatch(key=jnp.asarray(keys), **cols)


def _make_reqs(rng, name="svc"):
    """4 batches × 1000 Zipf-keyed RateLimitRequests.  Sections that
    must serve the SAME workload (svc object lane vs its wire lane;
    the cluster row vs round-2's recorded numbers) all draw these from
    a fresh seed-7 rng, so the bytes are identical across sections and
    rounds."""
    from gubernator_tpu.types import RateLimitRequest

    return [[RateLimitRequest(name=name, unique_key=f"k{int(k)}",
                              hits=1, limit=100, duration=60_000)
             for k in rng.zipf(ZIPF_A, size=1000) % 100_000]
            for _ in range(4)]


def _telemetry_rows(inst) -> dict:
    """Dispatcher wave-telemetry snapshot for a section's BENCH row
    (wave-size/step-duration percentiles, stall/timeout counts — see
    OBSERVABILITY.md).  A future perf round that loses a section to a
    slow wave diagnoses itself from this block instead of an empty
    TimeoutError (the round-5 failure shape)."""
    try:
        return inst.dispatcher.telemetry_snapshot()
    except Exception as e:  # noqa: BLE001 - telemetry must not cost rows
        return {"error": (str(e) or repr(e))[:200]}


def _analytics_rows(inst) -> dict:
    """ISSUE 4: the /debug/topkeys + /debug/phases snapshot for the
    BENCH row — which keys were hot and where the milliseconds went,
    auditable from the JSON alone.  Truncated to the heaviest 16."""
    ana = inst.analytics
    if ana is None:
        return {"skipped": "analytics disabled (GUBER_ANALYTICS=0)"}
    try:
        ana.flush(timeout=5.0)
        snap = ana.topkeys_snapshot(16)
        snap["phases"] = ana.phases_snapshot()["phases"]
        return snap
    except Exception as e:  # noqa: BLE001 - analytics must not cost rows
        return {"error": (str(e) or repr(e))[:200]}


def _analytics_ab(inst, call, pairs=5, reps=30) -> dict:
    """ISSUE 4 acceptance: the analytics tap must cost < 3 % throughput.
    Interleaved on/off timing pairs of the same call — detaching the
    ONE dispatcher.analytics reference darkens every tap — with the
    median of per-pair ratios cancelling the shared host's drift.  The
    ON arm flushes the worker's paced backlog before the OFF arm is
    timed (deferred fold work must not leak into the baseline), and an
    untimed warmup pair absorbs first-use costs (label children, fold
    buffers).  Skipped when analytics is off (no baseline)."""
    disp = inst.dispatcher
    ana = disp.analytics
    if ana is None:
        return {"skipped": "no analytics attached (GUBER_ANALYTICS=0)"}

    def rate():
        t0 = time.perf_counter()
        for r in range(reps):
            call(r)
        return reps / (time.perf_counter() - t0)

    try:
        ratios, on_r, off_r = [], [], []
        for pair in range(pairs + 1):
            disp.analytics = ana
            on = rate()
            ana.flush(timeout=5.0)
            disp.analytics = None
            off = rate()
            if pair == 0:
                continue  # warmup pair, untimed
            ratios.append(off / on)
            on_r.append(on)
            off_r.append(off)
        overhead = (float(np.median(ratios)) - 1.0) * 100
        row = {"overhead_pct": round(overhead, 2),
               "overhead_ok": bool(overhead < 3.0),
               "on_calls_per_s": round(float(np.median(on_r)), 1),
               "off_calls_per_s": round(float(np.median(off_r)), 1),
               "pairs": pairs, "reps": reps}
        if not row["overhead_ok"]:
            row["warning"] = ("analytics tap measured above the 3% "
                              "budget on this run; single-host noise — "
                              "re-run before acting on it")
        return row
    except Exception as e:  # noqa: BLE001 - diagnostics only
        return {"error": (str(e) or repr(e))[:200]}
    finally:
        disp.analytics = ana


def _tenant_ab(inst, call, pairs=5, reps=30) -> dict:
    """ISSUE 11 acceptance: tenant attribution must cost < 3 %
    throughput on top of the analytics tap.  Same interleaved-pair
    median discipline as ``_analytics_ab``, but the toggle is the
    ledger itself: detaching ``ana._tenants`` darkens every tenant
    fold/flag site while the rest of the analytics plane keeps
    running, so the measured delta is attribution alone."""
    disp = inst.dispatcher
    ana = disp.analytics
    if ana is None:
        return {"skipped": "no analytics attached (GUBER_ANALYTICS=0)"}
    ledger = ana._tenants
    if ledger is None:
        return {"skipped": "tenant ledger detached"}

    def rate():
        t0 = time.perf_counter()
        for r in range(reps):
            call(r)
        return reps / (time.perf_counter() - t0)

    try:
        ratios, on_r, off_r = [], [], []
        for pair in range(pairs + 1):
            ana._tenants = ledger
            on = rate()
            ana.flush(timeout=5.0)  # paced tenant folds out of OFF arm
            ana._tenants = None
            off = rate()
            if pair == 0:
                continue  # warmup pair, untimed
            ratios.append(off / on)
            on_r.append(on)
            off_r.append(off)
        overhead = (float(np.median(ratios)) - 1.0) * 100
        row = {"overhead_pct": round(overhead, 2),
               "overhead_ok": bool(overhead < 3.0),
               "on_calls_per_s": round(float(np.median(on_r)), 1),
               "off_calls_per_s": round(float(np.median(off_r)), 1),
               "pairs": pairs, "reps": reps}
        if not row["overhead_ok"]:
            row["warning"] = ("tenant attribution measured above the "
                              "3% budget on this run; single-host "
                              "noise — re-run before acting on it")
        return row
    except Exception as e:  # noqa: BLE001 - diagnostics only
        return {"error": (str(e) or repr(e))[:200]}
    finally:
        ana._tenants = ledger


def _faults_ab(inst, call, pairs=5, reps=30) -> dict:
    """ISSUE 5 acceptance: fault injection must be zero-cost while
    disarmed (<1% on the service path with GUBER_FAULT unset).

    Interleaved timing pairs of the same call in three states:
    *disarmed* (the shipping default — every instrumented site pays one
    attribute read), *detached* (the FaultSet reference removed /
    stubbed, the closest runtime proxy for uninstrumented code), and
    *armed* on an off-path point (``snapshot:error`` — the gate is hot,
    every site pays the lock + match).  ``disarmed_overhead_pct`` is
    the acceptance number (disarmed vs detached); ``armed_noop_pct``
    records what arming costs, i.e. what the disarmed gate saves.  The
    true pre-instrumentation baseline is the row's recorded pre-PR
    trajectory (concurrent16)."""
    disp = inst.dispatcher
    fs = inst.faults

    class _Detached:  # armed=False: byte-for-byte the disarmed branch
        armed = False

    dummy = _Detached()

    def rate():
        t0 = time.perf_counter()
        for r in range(reps):
            call(r)
        return reps / (time.perf_counter() - t0)

    def _state(which):
        if which == "det":
            inst.faults = dummy
            disp._faults = None
            return
        inst.faults = fs
        disp._faults = fs
        fs.arm("snapshot:error" if which == "arm" else "")

    def _measure(which):
        _state(which)
        try:
            return rate()
        finally:
            _state("dis")

    try:
        r_dis, r_det, r_arm = [], [], []
        for pair in range(pairs + 1):
            # alternate order per pair so monotonic host drift cancels
            # in the per-pair ratios instead of biasing them
            order = (("dis", "det", "arm") if pair % 2
                     else ("arm", "det", "dis"))
            got = {w: _measure(w) for w in order}
            if pair == 0:
                continue  # warmup pair, untimed
            r_dis.append(got["dis"])
            r_det.append(got["det"])
            r_arm.append(got["arm"])
        disarmed = (float(np.median([d / x for d, x
                                     in zip(r_det, r_dis)])) - 1) * 100
        armed = (float(np.median([d / x for d, x
                                  in zip(r_dis, r_arm)])) - 1) * 100
        row = {"disarmed_overhead_pct": round(disarmed, 2),
               "overhead_ok": bool(disarmed < 1.0),
               "armed_noop_pct": round(armed, 2),
               "disarmed_calls_per_s": round(float(np.median(r_dis)), 1),
               "pairs": pairs, "reps": reps}
        if not row["overhead_ok"]:
            row["warning"] = ("disarmed faultpoint checks measured "
                              "above the 1% budget on this run; "
                              "single-host noise — re-run before "
                              "acting on it")
        return row
    except Exception as e:  # noqa: BLE001 - diagnostics only
        return {"error": (str(e) or repr(e))[:200]}
    finally:
        inst.faults = fs
        disp._faults = fs
        try:
            fs.arm("")
        except Exception:  # noqa: BLE001
            pass


def _tracing_ab(inst, call, pairs=5, reps=30) -> dict:
    """ISSUE 12 acceptance: the trace plane must stay off the hot path
    — armed-but-unsampled (the shipping default, GUBER_TRACE_SAMPLE=0)
    < 1% on the service path, 1%-sampled < 3%.

    Interleaved timing pairs of the same call in three states: *off*
    (span recorder detached from the dispatcher AND the request
    context — the pre-instrumentation proxy), *armed* (recorder
    attached, sample=0: every request pays span buffering + a
    commit-and-drop), and *sampled* (sample=0.01: the realistic prod
    rate, ~1 in 100 traces retained into the ring).  Every arm wraps
    the call in ``tracing.request_context`` so the trace-id plumbing
    itself (pre-ISSUE 12 behavior) is in the baseline; only the span
    plane toggles.  Same alternating-order median-of-ratios discipline
    as ``_faults_ab``."""
    from gubernator_tpu.tracing import request_context

    disp = inst.dispatcher
    rec = inst.span_recorder
    old_sample = rec.sample

    state = {"rec": None}

    def rate():
        r_ctx = state["rec"]
        t0 = time.perf_counter()
        for r in range(reps):
            with request_context(None, recorder=r_ctx):
                call(r)
        return reps / (time.perf_counter() - t0)

    def _state(which):
        if which == "off":
            disp.span_recorder = None
            state["rec"] = None
            return
        disp.span_recorder = rec
        state["rec"] = rec
        rec.sample = 0.01 if which == "smp" else 0.0

    def _measure(which):
        _state(which)
        try:
            return rate()
        finally:
            _state("off")

    try:
        r_off, r_arm, r_smp = [], [], []
        for pair in range(pairs + 1):
            # alternate order per pair so monotonic host drift cancels
            # in the per-pair ratios instead of biasing them
            order = (("off", "arm", "smp") if pair % 2
                     else ("smp", "arm", "off"))
            got = {w: _measure(w) for w in order}
            if pair == 0:
                continue  # warmup pair, untimed
            r_off.append(got["off"])
            r_arm.append(got["arm"])
            r_smp.append(got["smp"])
        armed = (float(np.median([o / a for o, a
                                  in zip(r_off, r_arm)])) - 1) * 100
        sampled = (float(np.median([o / s for o, s
                                    in zip(r_off, r_smp)])) - 1) * 100
        row = {"armed_overhead_pct": round(armed, 2),
               "overhead_ok": bool(armed < 1.0),
               "sampled_overhead_pct": round(sampled, 2),
               "sampled_ok": bool(sampled < 3.0),
               "off_calls_per_s": round(float(np.median(r_off)), 1),
               "pairs": pairs, "reps": reps}
        if not (row["overhead_ok"] and row["sampled_ok"]):
            row["warning"] = ("trace plane measured above budget "
                              "(armed<1% / 1%-sampled<3%) on this "
                              "run; single-host noise — re-run "
                              "before acting on it")
        return row
    except Exception as e:  # noqa: BLE001 - diagnostics only
        return {"error": (str(e) or repr(e))[:200]}
    finally:
        disp.span_recorder = rec
        rec.sample = old_sample


def _memledger_ab(inst, call, pairs=5, reps=30) -> dict:
    """ISSUE 13 acceptance: the device-memory ledger must stay off the
    hot path — enrollment is registration-only and probes run on the
    SLO tick / scrape threads, so steady-state serving overhead must
    pin < 1%.

    Interleaved timing pairs of the same call in two states: *off*
    (ledger suspended — snapshots answer empty, nothing else changes)
    and *on* (the shipping default; one out-of-band pressure_sample
    between blocks keeps the plane exercised the way the 1 Hz SLO tick
    does without charging tick work to the serving thread).  Same
    alternating-order median-of-ratios discipline as ``_tracing_ab``."""
    led = getattr(inst, "memledger", None)
    if led is None:
        return {"error": "memory ledger disabled (GUBER_MEM_LEDGER=0)"}

    def rate():
        t0 = time.perf_counter()
        for r in range(reps):
            call(r)
        return reps / (time.perf_counter() - t0)

    def _measure(which):
        if which == "on":
            led.resume()
            led.pressure_sample()  # untimed: tick-thread work in prod
        else:
            led.suspend()
        try:
            return rate()
        finally:
            led.suspend()

    try:
        r_on, r_off = [], []
        for pair in range(pairs + 1):
            # alternate order per pair so monotonic host drift cancels
            order = ("off", "on") if pair % 2 else ("on", "off")
            got = {w: _measure(w) for w in order}
            if pair == 0:
                continue  # warmup pair, untimed
            r_on.append(got["on"])
            r_off.append(got["off"])
        overhead = (float(np.median([o / n for o, n
                                     in zip(r_off, r_on)])) - 1) * 100
        row = {"overhead_pct": round(overhead, 2),
               "overhead_ok": bool(overhead < 1.0),
               "on_calls_per_s": round(float(np.median(r_on)), 1),
               "off_calls_per_s": round(float(np.median(r_off)), 1),
               "pairs": pairs, "reps": reps}
        if not row["overhead_ok"]:
            row["warning"] = ("memory ledger measured above its <1% "
                              "budget on this run; single-host noise "
                              "— re-run before acting on it")
        return row
    except Exception as e:  # noqa: BLE001 - diagnostics only
        return {"error": (str(e) or repr(e))[:200]}
    finally:
        led.resume()


def _hbm_block(inst):
    """Standardized ledger sub-block for the engine rows (6/11/12/13,
    ISSUE 13): bytes + occupancy per consumer from ONE snapshot, so
    rows compare like-for-like instead of each growing ad-hoc
    occupancy fields."""
    led = getattr(inst, "memledger", None)
    if led is None:
        return None
    try:
        snap = led.snapshot()
        out = {"device_bytes": snap["device_bytes"],
               "host_bytes": snap["host_bytes"],
               "pressure": round(snap["pressure"], 4)}
        for name, rec in snap["consumers"].items():
            if "error" in rec:
                continue
            out[name] = {"bytes": rec["bytes"],
                         "capacity_rows": rec["capacity_rows"],
                         "occupied_rows": rec["occupied_rows"]}
        return out
    except Exception as e:  # noqa: BLE001 - diagnostics only
        return {"error": (str(e) or repr(e))[:200]}


def _serialize_reqs(reqs_lists):
    """[[RateLimitRequest]] → serialized GetRateLimitsReq bytes."""
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.wire import req_to_pb

    datas = []
    for rs in reqs_lists:
        m = pb.GetRateLimitsReq()
        m.requests.extend(req_to_pb(r) for r in rs)
        datas.append(m.SerializeToString())
    return datas


def _sec_lat_client():
    """Client-shaped device latency: one 1024-row batch per synced call
    (the p99<2ms target's shape) over a CAP-sized table."""
    import jax.numpy as jnp

    from gubernator_tpu.core.step import decide_batch, decide_batch_donated
    from gubernator_tpu.core.table import init_table

    step = (decide_batch_donated
            if os.environ.get("GUBER_BENCH_STEP_MODE") == "donate"
            else decide_batch)
    i64 = jnp.int64
    rng = np.random.default_rng(42)
    Bc = 1024
    keys = _keyhash((rng.zipf(ZIPF_A, size=Bc) % N_KEYS).astype(np.uint64))
    small = _mk_batch(jnp, keys)
    state = init_table(CAP)
    state, outc = step(state, small, jnp.asarray(NOW0, i64))
    outc.status.block_until_ready()
    lats = []
    for i in range(100):
        t0 = time.perf_counter()
        state, outc = step(state, small, jnp.asarray(NOW0 + 1 + i, i64))
        outc.status.block_until_ready()
        lats.append((time.perf_counter() - t0) * 1e3)
    return {"client_batch_p50_ms": round(float(np.percentile(lats, 50)), 3),
            "client_batch_p99_ms": round(float(np.percentile(lats, 99)), 3)}


def _sec_scan():
    """Device-resident superstep: lax.scan chains R batches in ONE
    launch, so per-launch dispatch latency amortizes across R×B
    decisions — the on-chip sustained rate, which is what N coalesced
    client batches see."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from gubernator_tpu.core.batch import RequestBatch
    from gubernator_tpu.core.step import decide_batch_impl
    from gubernator_tpu.core.table import init_table

    i64 = jnp.int64
    R = int(os.environ.get("GUBER_BENCH_SCAN", 16))
    rng = np.random.default_rng(42)
    n_batches = 8
    draws = rng.zipf(ZIPF_A, size=n_batches * B) % N_KEYS
    kb = [jnp.asarray(_keyhash(draws[i * B:(i + 1) * B].astype(np.uint64)))
          for i in range(n_batches)]
    const = dict(
        hits=jnp.ones(B, i64), limit=jnp.full(B, LIMIT, i64),
        duration=jnp.full(B, DURATION_MS, i64),
        eff_ms=jnp.full(B, DURATION_MS, i64),
        greg_end=jnp.zeros(B, i64), behavior=jnp.zeros(B, jnp.int32),
        algorithm=jnp.zeros(B, jnp.int32), burst=jnp.full(B, LIMIT, i64),
        valid=jnp.ones(B, bool))

    @jax.jit
    def decide_scan(st, keys_rb, now0):
        def body(carry, x):
            st, i = carry
            b = RequestBatch(key=x, **const)
            st, out = decide_batch_impl(st, b, now0 + i)
            return (st, i + 1), out.status.sum()
        (st, _), overs = lax.scan(body, (st, jnp.asarray(0, i64)), keys_rb)
        return st, overs

    keys_rb = jnp.stack(kb[:min(R, n_batches)] * (R // n_batches + 1))[:R]
    st_s = init_table(CAP)
    st_s, ov = decide_scan(st_s, keys_rb, jnp.asarray(NOW0, i64))
    ov.block_until_ready()  # compile + warm
    reps_s = max(1, int(30_000_000 / (R * B)))
    bump_R = _bump_fn(R)  # device-side now advance: no host→device
    # transfer between launches
    # warm outside the timed region: NOW0+1000-R → NOW0+1000
    now_dev = bump_R(jnp.asarray(NOW0 + 1000 - R, i64))
    now_dev.block_until_ready()
    t0 = time.perf_counter()
    for r in range(reps_s):
        st_s, ov = decide_scan(st_s, keys_rb, now_dev)
        now_dev = bump_R(now_dev)
    ov.block_until_ready()
    dps_scan = reps_s * R * B / (time.perf_counter() - t0)
    return {"device_scan_decisions_per_s": round(dps_scan),
            "scan_R": R}


def _sec_cfg12():
    """Configs 1+2: single-key TOKEN smoke (the duplicate-segment worst
    case) and LEAKY 1k keys."""
    import jax.numpy as jnp

    from gubernator_tpu.core.step import decide_batch
    from gubernator_tpu.core.table import init_table

    i64, i32 = jnp.int64, jnp.int32
    rng = np.random.default_rng(7)
    out = {}
    Bs = 4096
    try:
        keys1 = np.full(Bs, 12345, np.uint64)
        st = init_table(1 << 12)
        b = _mk_batch(jnp, keys1, limit=jnp.full(Bs, 10**9, i64))
        st, _ = decide_batch(st, b, jnp.asarray(NOW0, i64))  # compile
        dps1, _ = _sustain(decide_batch, jnp, st, [b], 20, NOW0 + 1)
        out["1_single_key_smoke"] = {"decisions_per_s": round(dps1)}
    except Exception as e:  # noqa: BLE001
        out["1_single_key_smoke"] = {"error": (str(e) or repr(e))[:200]}
    try:
        keys2 = _keyhash(rng.integers(0, 1000, size=Bs).astype(np.uint64))
        st = init_table(1 << 12)
        b2 = _mk_batch(jnp, keys2, algorithm=jnp.ones(Bs, i32),
                       limit=jnp.full(Bs, 10**6, i64),
                       burst=jnp.full(Bs, 10**6, i64),
                       duration=jnp.full(Bs, 60_000, i64),
                       eff_ms=jnp.full(Bs, 60_000, i64))
        st, _ = decide_batch(st, b2, jnp.asarray(NOW0, i64))
        dps2, _ = _sustain(decide_batch, jnp, st, [b2], 20, NOW0 + 1)
        out["2_leaky_1k_keys"] = {"decisions_per_s": round(dps2)}
    except Exception as e:  # noqa: BLE001
        out["2_leaky_1k_keys"] = {"error": (str(e) or repr(e))[:200]}
    return out


def _sec_cfg4():
    """Config 4: GLOBAL multi-peer ≙ sharded mesh step over all local
    devices (4-chip ICI on a pod; 1 chip here → shard_map overhead)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gubernator_tpu.core.batch import RequestBatch
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.mesh import shard_table
    from gubernator_tpu.parallel.sharded import make_sharded_step

    i64 = jnp.int64
    rng = np.random.default_rng(7)
    mesh = make_mesh()
    n = mesh.shape["shard"]
    step = make_sharded_step(mesh)
    stg = shard_table(mesh, 1 << 18)
    Bg = 16384 * n
    keysg = _keyhash(rng.zipf(ZIPF_A, size=Bg) % 100_000)
    bg = _mk_batch(jnp, keysg)
    sh = NamedSharding(mesh, P("shard"))
    bg = RequestBatch(*[jax.device_put(np.asarray(x), sh) for x in bg])
    stg, o, _ = step(stg, bg, jnp.asarray(NOW0, i64))
    bump = _bump_fn()  # transfer-free now advance, warmed pre-timing
    now_dev = bump(jnp.asarray(NOW0, i64))  # NOW0 → NOW0+1
    now_dev.block_until_ready()
    t0 = time.perf_counter()
    reps = 20
    for r in range(reps):
        stg, o, _ = step(stg, bg, now_dev)
        now_dev = bump(now_dev)
    o[0].block_until_ready()
    dps4 = reps * Bg / (time.perf_counter() - t0)
    row = {"decisions_per_s": round(dps4), "n_shards": int(n)}
    if n == 1:
        row["context"] = ("single device: pays shard_map overhead with "
                          "no scaling; per-shard cost is flat 1→8 on "
                          "the virtual mesh (BASELINE.md weak-scaling "
                          "table)")
    return {"4_global_sharded": row}


def _section_checkpoint(rows: dict) -> None:
    """Per-lane checkpoint: sections with several independent lanes
    (svc has three-plus) write finished lanes to the section-out path
    (GUBER_BENCH_SECTION_OUT, single-section runs) as they land, so a
    run killed at its time limit keeps every lane measured before the
    kill."""
    path = os.environ.get("GUBER_BENCH_SECTION_OUT")
    if not path:
        return
    try:
        with open(path + ".tmp", "w") as f:
            json.dump(rows, f)
        os.replace(path + ".tmp", path)
    except OSError as e:  # pragma: no cover - diagnostics only
        log(f"section checkpoint write failed: {e}")


def _sec_svc():
    """Service path: full V1Instance routing + dispatcher + response
    assembly (benchmark_test.go › BenchmarkServer_GetRateLimit analog),
    its C++ wire lane, the 16-thread concurrent front door, and the
    peer-forwarding apply path (BenchmarkServer_GetPeerRateLimit).
    Each lane checkpoints as it finishes (_section_checkpoint)."""
    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.parallel import make_mesh

    rng = np.random.default_rng(7)
    out = {}
    inst = V1Instance(Config(cache_size=1 << 16, sweep_interval_ms=0),
                      mesh=make_mesh(n=1))
    try:
        reqs5 = _make_reqs(rng)
        inst.get_rate_limits(reqs5[0], now_ms=NOW0)
        t0 = time.perf_counter()
        reps = 20
        for r in range(reps):
            inst.get_rate_limits(reqs5[r % 4], now_ms=NOW0 + 1 + r)
        dps_svc = reps * 1000 / (time.perf_counter() - t0)
        out["6_service_path"] = {"decisions_per_s": round(dps_svc),
                                 "batch": 1000}
        _section_checkpoint(out)
        # the C++ wire lane (bytes → columns → device → bytes), the
        # path a gRPC client actually exercises
        try:
            # same 4000 requests through the wire lane as through the
            # object lane above — both lanes serve identical batches
            datas = _serialize_reqs(reqs5)
            inst.get_rate_limits_wire(datas[0], now_ms=NOW0 + 100)
            t0 = time.perf_counter()
            for r in range(reps):
                inst.get_rate_limits_wire(datas[r % 4],
                                          now_ms=NOW0 + 101 + r)
            out["6_service_path"]["wire_lane_decisions_per_s"] = round(
                reps * 1000 / (time.perf_counter() - t0))
            # service-layer latency at the client-batch shape (the
            # p99 < 2 ms target's request): bytes → decisions → bytes
            lat = []
            for r in range(60):
                t0 = time.perf_counter()
                inst.get_rate_limits_wire(datas[r % 4],
                                          now_ms=NOW0 + 130 + r)
                lat.append((time.perf_counter() - t0) * 1e3)
            out["6_service_path"]["svc_p50_ms"] = round(
                float(np.percentile(lat, 50)), 3)
            out["6_service_path"]["svc_p99_ms"] = round(
                float(np.percentile(lat, 99)), 3)
        except Exception as e:  # noqa: BLE001
            out["6_service_path"]["wire_lane_error"] = (str(e) or repr(e))[:200]
        # ISSUE 14 acceptance: the compile ledger proves the warmed
        # service path is retrace-stable — mark steady AFTER the loops
        # above compiled everything, serve another measured burst, and
        # record the verdict (steady_recompiles must be empty; the
        # static twin is guberlint's retrace pass)
        try:
            led = inst.compile_ledger
            led.mark_steady()
            for r in range(10):
                inst.get_rate_limits_wire(datas[r % 4],
                                          now_ms=NOW0 + 300 + r)
            out["6_service_path"]["compile_ledger"] = led.verdict()
        except Exception as e:  # noqa: BLE001
            out["6_service_path"]["compile_ledger"] = {
                "error": (str(e) or repr(e))[:200]}
        _section_checkpoint(out)
        # concurrent front door: 16 caller threads through the full
        # wire lane — the dispatcher coalesces them into shared waves
        try:
            import threading as _th

            n_threads, reps_c = 16, 8
            if hasattr(inst.engine, "warmup"):
                inst.engine.warmup()  # big-bucket program, outside timing
            inst.get_rate_limits_wire(datas[0], now_ms=NOW0 + 150)

            def _worker(t):
                for r in range(reps_c):
                    inst.get_rate_limits_wire(datas[(t + r) % 4],
                                              now_ms=NOW0 + 160 + r)

            ths = [_th.Thread(target=_worker, args=(t,))
                   for t in range(n_threads)]
            t0 = time.perf_counter()
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            out["6_service_path"]["concurrent16_decisions_per_s"] = round(
                n_threads * reps_c * 1000 / (time.perf_counter() - t0))
            # ISSUE 2 acceptance record: the pre-PR value measured on
            # the same 1-core build host (pre-PR tree + the jax-compat
            # shim only), so the overlapped-pipeline speedup is
            # auditable from this JSON alone
            out["6_service_path"][
                "concurrent16_pre_pr_decisions_per_s"] = 348177
            out["6_service_path"]["pre_pr_context"] = (
                "pre-PR baseline measured 2026-08-04 on the 1-core "
                "build host (CPU backend); comparable only on that "
                "host class — PERF.md §8")
        except Exception as e:  # noqa: BLE001
            out["6_service_path"]["concurrent_error"] = (str(e) or repr(e))[:200]
        _section_checkpoint(out)
        # host-glue decomposition (tools/hostpath_prof.py): the §4.2
        # buckets measured live on this instance — a perf round reads
        # parse/pack vs dispatcher/future vs build straight from the
        # BENCH row instead of re-deriving them with cProfile by hand
        try:
            from tools.hostpath_prof import profile_wire_calls

            out["6_service_path"]["host_glue"] = profile_wire_calls(
                inst, datas, reps=10, now0=NOW0 + 400)
        except Exception as e:  # noqa: BLE001
            out["6_service_path"]["host_glue_error"] = (
                str(e) or repr(e))[:200]
        # ISSUE 4: tap overhead A/B on the wire lane (<3%, skip-if-no-
        # baseline) — same request bytes as the measured loops above
        try:
            out["6_service_path"]["analytics_ab"] = _analytics_ab(
                inst, lambda r: inst.get_rate_limits_wire(
                    datas[r % 4], now_ms=NOW0 + 500 + r))
        except Exception as e:  # noqa: BLE001
            out["6_service_path"]["analytics_ab"] = {
                "error": (str(e) or repr(e))[:200]}
        # ISSUE 11 acceptance: tenant attribution overhead A/B on the
        # same wire-lane call (<3% on top of the analytics tap)
        try:
            out["6_service_path"]["tenant_ab"] = _tenant_ab(
                inst, lambda r: inst.get_rate_limits_wire(
                    datas[r % 4], now_ms=NOW0 + 600 + r))
        except Exception as e:  # noqa: BLE001
            out["6_service_path"]["tenant_ab"] = {
                "error": (str(e) or repr(e))[:200]}
        # ISSUE 5 acceptance: disarmed faultpoint checks must cost <1%
        # on the service path (same request bytes as the loops above)
        try:
            out["6_service_path"]["faults_ab"] = _faults_ab(
                inst, lambda r: inst.get_rate_limits_wire(
                    datas[r % 4], now_ms=NOW0 + 700 + r))
        except Exception as e:  # noqa: BLE001
            out["6_service_path"]["faults_ab"] = {
                "error": (str(e) or repr(e))[:200]}
        # ISSUE 12 acceptance: trace-plane overhead A/B on the same
        # wire-lane call (armed-unsampled <1%, 1%-sampled <3%)
        try:
            out["6_service_path"]["tracing_ab"] = _tracing_ab(
                inst, lambda r: inst.get_rate_limits_wire(
                    datas[r % 4], now_ms=NOW0 + 800 + r))
        except Exception as e:  # noqa: BLE001
            out["6_service_path"]["tracing_ab"] = {
                "error": (str(e) or repr(e))[:200]}
        # ISSUE 13 acceptance: device-memory ledger overhead A/B on
        # the same wire-lane call (steady-state <1%)
        try:
            out["6_service_path"]["memledger_ab"] = _memledger_ab(
                inst, lambda r: inst.get_rate_limits_wire(
                    datas[r % 4], now_ms=NOW0 + 900 + r))
        except Exception as e:  # noqa: BLE001
            out["6_service_path"]["memledger_ab"] = {
                "error": (str(e) or repr(e))[:200]}
        _section_checkpoint(out)
        # peer-forwarding path: what the owner-side apply of a
        # forwarded batch takes, via its wire lane (since ISSUE 3 the
        # fused C++ ingest: received TLV bytes → leased packed wave →
        # device → response bytes).  Same harness shape as the pre-PR
        # rounds (sequential 1000-req applies), so the pre/post ratio
        # is like-for-like.
        try:
            from gubernator_tpu.proto import peers_pb2 as peers_pb
            from gubernator_tpu.wire import req_to_pb

            pdatas = []
            for rs in reqs5:
                m = peers_pb.GetPeerRateLimitsReq()
                m.requests.extend(req_to_pb(r) for r in rs)
                pdatas.append(m.SerializeToString())
            inst.get_peer_rate_limits_wire(pdatas[0], now_ms=NOW0 + 200)
            t0 = time.perf_counter()
            for r in range(reps):
                inst.get_peer_rate_limits_wire(pdatas[r % 4],
                                               now_ms=NOW0 + 201 + r)
            out["8_peer_path"] = {
                "decisions_per_s": round(
                    reps * 1000 / (time.perf_counter() - t0)),
                "batch": 1000,
                # ISSUE 3 acceptance record: the same loop measured on
                # this host at the pre-PR tree (HEAD^ worktree, median
                # of 3 runs), so the columnar-ingest speedup audits
                # from this JSON alone
                "pre_pr_decisions_per_s": 342870,
                "pre_pr_context": (
                    "pre-PR baseline measured 2026-08-04 on this "
                    "1-core build host (CPU backend, median of 3 "
                    "same-harness runs; run-to-run spread ~±15% on "
                    "this shared host) — PERF.md §9")}
            # ISSUE 4: tap overhead A/B on the forwarded-hop apply path
            out["8_peer_path"]["analytics_ab"] = _analytics_ab(
                inst, lambda r: inst.get_peer_rate_limits_wire(
                    pdatas[r % 4], now_ms=NOW0 + 600 + r))
        except Exception as e:  # noqa: BLE001
            out["8_peer_path"] = {"error": (str(e) or repr(e))[:200]}
        if "6_service_path" in out:
            out["6_service_path"]["telemetry"] = _telemetry_rows(inst)
            # ISSUE 4: which keys were hot + where the ms went, straight
            # in the BENCH row (top-16 of the ledger + the phase ledger)
            out["6_service_path"]["analytics"] = _analytics_rows(inst)
            # ISSUE 13: the standardized per-consumer memory block
            out["6_service_path"]["hbm"] = _hbm_block(inst)
    finally:
        inst.close()
    return out


def _sec_cluster():
    """Clustered service path (VERDICT r1 item 4's bench criterion):
    client-facing GetRateLimits through daemon 0 of a real 3-daemon
    loopback cluster, keys ring-split across owners, forwards riding
    the raw-TLV peer wire."""
    from gubernator_tpu import cluster as cluster_mod

    # identical bytes to the svc section's wire batches (fresh seed-7
    # rng draws the same keys) — intra-run svc↔cluster identity.  NOTE:
    # the section refactor changed the RNG stream vs rounds ≤2 (one
    # shared seed-7 rng used to be consumed in order across cfg2/cfg4/
    # svc); rows 4/6/8/9/10 workload bytes are comparable only within
    # and after round 3 (recorded in BASELINE.md).
    datas = _serialize_reqs(_make_reqs(np.random.default_rng(7)))
    c3 = cluster_mod.start(3, cache_size=1 << 14, batch_rows=1024)
    try:
        inst0 = c3.instance_at(0)
        reps = 12
        inst0.get_rate_limits_wire(datas[0], now_ms=NOW0 + 300)
        t0 = time.perf_counter()
        for r in range(reps):
            inst0.get_rate_limits_wire(datas[r % 4],
                                       now_ms=NOW0 + 301 + r)
        dps_c3 = reps * 1000 / (time.perf_counter() - t0)
        lane = inst0.metrics.wire_lane_counter.labels(
            lane="wire_clustered")._value.get()
        # conservation (ISSUE 3 acceptance): one shared key drained
        # through ALL THREE daemons must debit exactly once per hit —
        # ring ownership + the pooled forward lanes must not lose,
        # duplicate, or misroute a request
        conserved = None
        try:
            from gubernator_tpu.proto import gubernator_pb2 as _pb

            def _one(hits):
                m = _pb.GetRateLimitsReq()
                rq = m.requests.add()
                rq.name, rq.unique_key = "c3cons", "shared"
                rq.hits, rq.limit, rq.duration = hits, 10**6, 600_000
                return m.SerializeToString()

            for d in range(3):
                c3.instance_at(d).get_rate_limits_wire(
                    _one(5), now_ms=NOW0 + 400 + d)
            q = _pb.GetRateLimitsResp.FromString(
                inst0.get_rate_limits_wire(_one(0), now_ms=NOW0 + 410))
            conserved = int(q.responses[0].remaining) == 10**6 - 15
        except Exception as e:  # noqa: BLE001
            conserved = f"check failed: {(str(e) or repr(e))[:120]}"
        row = {"decisions_per_s": round(dps_c3), "daemons": 3,
               "wire_clustered_requests": int(lane),
               "conservation_exact": conserved,
               "telemetry": _telemetry_rows(inst0)}
        # ISSUE 5: degraded-mode throughput vs the healthy baseline —
        # fault-kill one owner's forwards (faults.py) and remeasure the
        # same loop; rows owned by the dead peer answer locally with
        # the degraded flag instead of error rows.  The first reps pay
        # retry+backoff until the circuit opens, then fail-fast +
        # local serve — that transition is part of the number.
        try:
            vaddr = c3.peer_at(2).grpc_address
            inst0.faults.arm(f"peer_send@{vaddr}:error", seed=7)
            inst0.get_rate_limits_wire(datas[0], now_ms=NOW0 + 500)
            t0 = time.perf_counter()
            for r in range(reps):
                inst0.get_rate_limits_wire(datas[r % 4],
                                           now_ms=NOW0 + 501 + r)
            dps_deg = reps * 1000 / (time.perf_counter() - t0)
            fam = inst0.metrics.degraded_served.collect()[0]
            deg_rows = sum(s.value for s in fam.samples
                           if s.name.endswith("_total"))
            row["degraded"] = {
                "decisions_per_s": round(dps_deg),
                "vs_healthy": round(dps_deg / dps_c3, 3),
                "degraded_rows_served": int(deg_rows),
                "context": ("one of three owners' forwards fault-"
                            "killed (peer_send@addr:error); its keys "
                            "serve degraded from the local shard — "
                            "RESILIENCE.md")}
        except Exception as e:  # noqa: BLE001
            row["degraded"] = {"error": (str(e) or repr(e))[:200]}
        finally:
            try:
                inst0.faults.clear()
            except Exception:  # noqa: BLE001
                pass
        cores = _host_cores()
        if cores < 3:
            # VERDICT r2 weak #3: without this, the row reads as a
            # regression vs the single-daemon row
            row["context"] = (
                f"{cores}-core host serializes all 3 daemons; below "
                "the single-daemon row by construction, not a "
                "clustering regression (PERF.md §4.1)")
        return {"9_clustered_service": row}
    finally:
        c3.stop()


def _group_contention_probe(n_procs: int, reps_g: int) -> dict:
    """Small SO_REUSEPORT group on a starved host: verifies the group
    SURVIVES contention (no failed calls; a shared key drains exactly
    once per hit across connections/processes) and that the kernel
    actually spreads connections — the measurable ingredients of the
    ≥4-core scaling claim.  The rate is labeled as contention, never
    as scaling."""
    import threading as _th
    import urllib.request

    import grpc as _grpc

    from gubernator_tpu.cluster import start_subprocess_group

    gdatas = _serialize_reqs(_make_reqs(np.random.default_rng(7),
                                        name="grp"))
    grp = start_subprocess_group(n_procs, cache_size=1 << 14,
                                 batch_rows=1024)
    chans = []
    try:
        n_chan = 2 * n_procs
        chans = [_grpc.insecure_channel(
            grp.client_address,
            options=[("grpc.use_local_subchannel_pool", 1)])
            for _ in range(n_chan)]
        calls = [c.unary_unary("/pb.gubernator.V1/GetRateLimits")
                 for c in chans]
        for call in calls:
            call(gdatas[0], timeout=120)
        lat, errors = [[] for _ in range(n_chan)], []

        def _w(t):
            try:
                for r in range(reps_g):
                    t1 = time.perf_counter()
                    calls[t](gdatas[(t + r) % 4], timeout=120)
                    lat[t].append((time.perf_counter() - t1) * 1e3)
            except Exception as e:  # noqa: BLE001
                errors.append((str(e) or repr(e))[:120])

        ths = [_th.Thread(target=_w, args=(t,)) for t in range(n_chan)]
        t0 = time.perf_counter()
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        wall = time.perf_counter() - t0
        flat = [x for ls in lat for x in ls]
        # spread check: per-address scrape failures are
        # RECORDED (never `except: pass`), the expected lane labels
        # must actually exist in the exposition, and a check that
        # couldn't run reports `spread_check_failed` instead of a `0`
        # that contradicts the completed-calls count
        spread = 0
        spread_errors = []
        # a daemon that served ANY request shows one of these lanes
        lane_labels = ('lane="wire_local"', 'lane="wire_clustered"',
                       'lane="peer_wire"', 'lane="pb2_fallback"')
        for addr in grp.http_addresses:
            try:
                with urllib.request.urlopen(
                        f"http://{addr}/metrics", timeout=10) as f:
                    text = f.read().decode()
                lane_lines = [
                    line for line in text.splitlines()
                    if line.startswith(
                        "gubernator_wire_lane_requests_total")]
                if not any(lb in line for line in lane_lines
                           for lb in lane_labels):
                    # served traffic MUST label a lane; a scrape with
                    # none means the metric surface changed under us —
                    # flag it rather than counting a silent 0
                    spread_errors.append(
                        f"{addr}: no wire-lane labels in exposition "
                        f"({len(lane_lines)} lane lines)")
                    continue
                got = any(
                    line.split()[-1] not in ("0", "0.0")
                    for line in lane_lines
                    if ('lane="wire_local"' in line
                        or 'lane="wire_clustered"' in line))
                spread += bool(got)
            except Exception as e:  # noqa: BLE001
                spread_errors.append(
                    f"{addr}: scrape failed: {(str(e) or repr(e))[:120]}")
        # conservation: one key drained through every connection (the
        # kernel spreads them over processes) must debit exactly once
        # per hit — ring ownership, not per-process buckets
        conserved = None
        try:
            from gubernator_tpu.proto import gubernator_pb2 as _pb

            def _one(hits):
                m = _pb.GetRateLimitsReq()
                r = m.requests.add()
                r.name, r.unique_key = "grpcons", "shared"
                r.hits, r.limit, r.duration = hits, 10**6, 600_000
                return m.SerializeToString()

            for t in range(n_chan):
                calls[t](_one(3), timeout=120)
            q = _pb.GetRateLimitsResp.FromString(
                calls[0](_one(0), timeout=120))
            conserved = (int(q.responses[0].remaining)
                         == 10**6 - 3 * n_chan)
        except Exception as e:  # noqa: BLE001
            conserved = f"check failed: {(str(e) or repr(e))[:120]}"
        row = {f"contention_{n_procs}proc_decisions_per_s": round(
            len(flat) * 1000 / wall),
            "contention_completed_calls": len(flat),
            "contention_expected_calls": n_chan * reps_g,
            "conservation_exact": conserved,
            # a spread count the scrapes couldn't establish must say
            # so — a silent 0 next to N completed calls is a
            # contradiction, not a measurement.  With
            # partial scrape failures a non-zero count still stands as
            # a lower bound (errors recorded beside it).
            "processes_seeing_traffic": (
                "spread_check_failed"
                if spread_errors and spread == 0 else spread),
            "processes": n_procs}
        if spread_errors:
            row["spread_check_errors"] = spread_errors[:4]
        if flat:
            row["contention_p99_ms"] = round(
                float(np.percentile(flat, 99)), 3)
            cores = _host_cores()
            if cores < n_procs + 1:
                # r3→r4 this row swung 951 → 10,487 ms on the same
                # probe: on a starved host the percentile is scheduler
                # noise — the booleans above are the row's information
                row["contention_p99_context"] = (
                    f"{cores}-core host runs {n_procs} daemons + "
                    "workers on one scheduler: the percentile is "
                    "variance-dominated and NOT comparable across "
                    "runs; conservation_exact and "
                    "processes_seeing_traffic are the stable signals")
        if errors:
            row["contention_worker_errors"] = errors[:3]
        return row
    finally:
        for c in chans:
            try:
                c.close()
            except Exception:  # noqa: BLE001
                pass
        grp.stop()


def _sec_group():
    """SO_REUSEPORT front-door group: N daemon
    PROCESSES share one client gRPC port; kernel spreads connections;
    keys ring-split across per-process engines with raw-TLV peer
    forwards.  Runs on the CPU backend by design (subprocesses can't
    share the TPU chip; on a TPU host these are the ingest workers).
    Needs ≥4 host cores — on fewer the row self-skips honestly
    (measured 1-core thrash: 18k/s aggregate, p99 25 s)."""
    host_cores = _host_cores()
    if os.environ.get("GUBER_BENCH_SKIP_GROUP"):
        return {}
    if host_cores < 4:
        # Scaling is unmeasurable here, but the INGREDIENTS aren't:
        # run a small 2-process group anyway to verify correctness
        # under contention + kernel connection spreading, and record
        # the falsifiable aggregation model (BASELINE.md "Front-door
        # scaling model") its ≥4-core projection comes from.
        row = {
            "skipped_scaling": (
                f"host has {host_cores} core(s); the process-scaling "
                "number needs >=4 — rate below measures contention "
                "survival, not scaling"),
            "model": ("aggregate ~= N_procs * per_process_rate * "
                      "eff(0.5-0.7); per_process_rate = "
                      "6_service_path.concurrent16_decisions_per_s; "
                      "the (N-1)/N forward hop is inside eff"),
        }
        try:
            row.update(_group_contention_probe(n_procs=2, reps_g=8))
        except Exception as e:  # noqa: BLE001
            row["contention_error"] = (str(e) or repr(e))[:200]
        return {"10_reuseport_group": row}
    import threading as _th

    import grpc as _grpc

    from gubernator_tpu.cluster import start_subprocess_group

    gdatas = _serialize_reqs(_make_reqs(np.random.default_rng(7),
                                        name="grp"))
    n_procs = 2 if FAST else min(4, host_cores)
    grp = start_subprocess_group(n_procs, cache_size=1 << 16,
                                 batch_rows=1024)
    chans = []
    try:
        n_chan, reps_g = 4 * n_procs, 40
        chans = [_grpc.insecure_channel(
            grp.client_address,
            options=[("grpc.use_local_subchannel_pool", 1)])
            for _ in range(n_chan)]
        calls = [c.unary_unary("/pb.gubernator.V1/GetRateLimits")
                 for c in chans]
        # connect + warmup: timed traffic reuses these same
        # connections, and each warmup batch ring-forwards sub-batches
        # to EVERY process, so every engine has compiled its wave
        # program before timing starts
        for call in calls:
            call(gdatas[0], timeout=60)
        lat_g = [[] for _ in range(n_chan)]
        g_errors = []

        def _gworker(t):
            try:
                for r in range(reps_g):
                    t1 = time.perf_counter()
                    calls[t](gdatas[(t + r) % 4], timeout=60)
                    lat_g[t].append((time.perf_counter() - t1) * 1e3)
            except Exception as e2:  # noqa: BLE001
                g_errors.append(str(e2)[:120])

        ths = [_th.Thread(target=_gworker, args=(t,))
               for t in range(n_chan)]
        t0 = time.perf_counter()
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        wall = time.perf_counter() - t0
        # numerator = calls that actually completed: a daemon dying
        # mid-run must not inflate the rate
        flat = [x for ls in lat_g for x in ls]
        row = {"decisions_per_s": round(len(flat) * 1000 / wall),
               "processes": n_procs, "connections": n_chan}
        if flat:
            row["p50_ms"] = round(float(np.percentile(flat, 50)), 3)
            row["p99_ms"] = round(float(np.percentile(flat, 99)), 3)
        if g_errors:
            row["worker_errors"] = g_errors[:3]
        return {"10_reuseport_group": row}
    finally:
        for c in chans:
            try:
                c.close()
            except Exception:  # noqa: BLE001
                pass
        grp.stop()


def _sec_cfg5():
    """Config 5: huge multi-tenant table (100M keys → CAP 2^27),
    Gregorian resets + RESET_REMAINING churn.  The TRUE BASELINE.json
    capacity is attempted — never silently downscaled (VERDICT r1
    item 3): the donated step keeps ONE copy of the ~9 GB table live,
    which is what makes 2^27 fit a 16 GB chip at all.  The CPU
    fallback uses a reduced capacity and says so via "cpu_reduced"."""
    import jax
    import jax.numpy as jnp

    from gubernator_tpu.core.step import decide_batch_donated
    from gubernator_tpu.core.table import init_table
    from gubernator_tpu.gregorian import gregorian_expiration
    from gubernator_tpu.types import Behavior, GregorianDuration

    i64 = jnp.int64
    rng = np.random.default_rng(7)
    cpu5 = jax.default_backend() == "cpu"
    cap5 = 1 << 22 if cpu5 else 1 << 27
    try:
        n_keys5 = int(cap5 * 0.75)
        st5 = init_table(cap5)
        greg_end = gregorian_expiration(NOW0, int(GregorianDuration.HOURS))
        beh = int(Behavior.DURATION_IS_GREGORIAN)
        batches = []
        for i in range(4):
            k = _keyhash(rng.integers(0, n_keys5, size=B).astype(np.uint64))
            beh_col = np.full(B, beh, np.int32)
            beh_col[::37] |= int(Behavior.RESET_REMAINING)  # churn
            batches.append(_mk_batch(
                jnp, k,
                duration=jnp.full(B, int(GregorianDuration.HOURS), i64),
                eff_ms=jnp.full(B, 3_600_000, i64),
                greg_end=jnp.full(B, greg_end, i64),
                behavior=jnp.asarray(beh_col)))
        st5, _ = decide_batch_donated(st5, batches[0],
                                      jnp.asarray(NOW0, i64))
        dps5, _ = _sustain(decide_batch_donated, jnp, st5, batches, 16,
                           NOW0 + 1)
        return {"5_gregorian_churn": {"decisions_per_s": round(dps5),
                                      "capacity": cap5,
                                      "cpu_reduced": cpu5}}
    except Exception as e:  # noqa: BLE001
        return {"5_gregorian_churn": {"error": (str(e) or repr(e))[:200],
                                      "capacity_attempted": int(cap5)}}


def _sec_pallas():
    """GUBER_ENGINE=pallas as THE serving engine (ISSUE 8): the full
    V1Instance wire path — bytes → dispatcher → ONE fused device
    program per wave (decision kernel + on-device heavy-hitter tap) →
    bytes — A/B'd against the classic XLA engine on IDENTICAL seeded
    traffic.  On TPU the fused engine embeds the Mosaic bucket kernel
    at the large-CAP shape the mode exists for; on CPU it embeds the
    COMPILED small-shape XLA kernel (XlaFusedEngine) — the old
    interpret-mode toy row measured nothing and is gone (its number is
    recorded under pre_pr).  The row carries the A/B bit-identity, the
    fused/xla throughput ratio, and the PhaseLedger's in-wave partition
    of both engines (phase_partition)."""
    import jax

    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.pallas_engine import (
        PallasServingEngine, XlaFusedEngine)

    cpu = jax.default_backend() == "cpu"
    cap = 1 << 14 if cpu else 1 << 24  # 2 GiB of rows on-chip
    reps = 8 if FAST else (16 if cpu else 20)
    row = {"capacity": cap, "batch": 1000, "cpu_compiled": cpu,
           "engine": "xla_fused" if cpu else "pallas_fused",
           "compiled_kernels": True,
           # the row this one replaces: interpret-mode kernel at a toy
           # shape, self-described as measuring nothing (BENCH_r05)
           "pre_pr": {"wire_lane_decisions_per_s": 80411,
                      "mode": "interpret toy (BENCH_r05; 'measures "
                              "nothing')"}}
    datas = _serialize_reqs(_make_reqs(np.random.default_rng(7)))

    def drive(engine_sel):
        # env GUBER_STEP_IMPL / GUBER_ENGINE would override Config and
        # silently measure the wrong engine — pin both for this build
        prev_e = os.environ.get("GUBER_ENGINE")
        prev_i = os.environ.get("GUBER_STEP_IMPL")
        os.environ["GUBER_ENGINE"] = engine_sel
        os.environ.pop("GUBER_STEP_IMPL", None)
        try:
            inst = V1Instance(Config(cache_size=cap,
                                     sweep_interval_ms=0,
                                     engine=engine_sel),
                              mesh=make_mesh(n=1))
        finally:
            for k, v in (("GUBER_ENGINE", prev_e),
                         ("GUBER_STEP_IMPL", prev_i)):
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        try:
            inst.get_rate_limits_wire(datas[0], now_ms=NOW0)  # compile
            outs = []
            t0 = time.perf_counter()
            for r in range(reps):
                outs.append(inst.get_rate_limits_wire(
                    datas[r % len(datas)], now_ms=NOW0 + 1 + r))
            dps = reps * 1000 / (time.perf_counter() - t0)
            lat = []
            for r in range(8 if cpu else 60):
                t0 = time.perf_counter()
                inst.get_rate_limits_wire(datas[r % len(datas)],
                                          now_ms=NOW0 + 40 + r)
                lat.append((time.perf_counter() - t0) * 1e3)
            ana = inst.dispatcher.analytics
            phases = (ana.phases.snapshot() if ana is not None else {})
            # the exact wave-time partition is the proof of phase
            # deletion: sum(segments) == wave duration on every wave
            drift = 0.0
            for ev in inst.recorder.events(limit=256):
                if ev.get("kind") == "wave_completed" \
                        and ev.get("phases"):
                    drift = max(drift, abs(
                        sum(ev["phases"].values())
                        - ev["duration_ms"]))
            return {"dps": dps, "outs": outs, "phases": phases,
                    "lat": lat, "drift_ms": drift,
                    "engine_cls": type(inst.engine).__name__,
                    "fused_waves": getattr(inst.engine,
                                           "fused_wave_count", 0),
                    "hbm": _hbm_block(inst),
                    "telemetry": _telemetry_rows(inst)}
        finally:
            inst.close()

    fused = drive("pallas")
    xla = drive("xla")
    want = (XlaFusedEngine if cpu else PallasServingEngine).__name__
    assert fused["engine_cls"] == want, fused["engine_cls"]
    pmeans = {k: {p: v["p50_ms"] for p, v in d["phases"].items()
                  if p in ("pack", "device", "resolve")}
              for k, d in (("fused", fused), ("xla", xla))}
    row.update({
        "wire_lane_decisions_per_s": round(fused["dps"]),
        "xla_wire_decisions_per_s": round(xla["dps"]),
        "fused_vs_xla": round(fused["dps"] / max(xla["dps"], 1e-9), 3),
        "ab_identical": fused["outs"] == xla["outs"],
        "fused_waves": fused["fused_waves"],
        "svc_p50_ms": round(float(np.percentile(fused["lat"], 50)), 3),
        "svc_p99_ms": round(float(np.percentile(fused["lat"], 99)), 3),
        # ISSUE 13: the ad-hoc occupancy field became the standardized
        # per-consumer memory block (comparable across rows 6/11/12/13)
        "hbm": fused["hbm"],
        "telemetry": fused["telemetry"],
        # PhaseLedger evidence: both engines' waves carry pack, device
        # (in-flight time) and resolve, and the per-wave partition
        # stays exact (drift is float rounding).  This block used to
        # read an absent `pack` as proof that fusion deleted the host
        # work; the chip refuted that (PERF.md §5, ISSUE 24)
        "phase_partition": {
            "pack_in_fused": "pack" in fused["phases"],
            "pack_in_xla": "pack" in xla["phases"],
            "phase_p50_ms": pmeans,
            "partition_max_drift_ms": round(
                max(fused["drift_ms"], xla["drift_ms"]), 3)},
    })
    if cpu:
        row["context"] = (
            "CPU row serves from the COMPILED small-shape XLA fused "
            "flavor (GUBER_ENGINE=pallas off-TPU): decisions "
            "bit-identical to the classic engine by construction, so "
            "the A/B prices exactly what fusion deletes (host tap "
            "copies). The Mosaic bucket kernel at "
            "large CAP is the TPU row")
    return {"11_pallas_serving": row}


def _sec_mesh():
    """Pod-coherent GLOBAL over the mesh (ISSUE 7): the same seeded
    GLOBAL wire traffic served twice — GUBER_GLOBAL_MODE=mesh (the
    collective-reconcile tier, zero gRPC peer RPCs) vs grpc (the
    reference hit-queue path: the owner rows of the sharded table serve)
    — with the A/B bit-identity, exact-conservation verdict, reconcile
    generations, and measured coherence staleness recorded in the row."""
    import jax

    from gubernator_tpu.config import BehaviorConfig, Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.types import Behavior, RateLimitRequest

    sync_ms = 100
    reps = 4 if FAST else 16
    rng = np.random.default_rng(7)
    # bounded key domain: every key pins into the mesh tier (the row
    # measures the collective path, not pin-fail fallbacks)
    batches = [[RateLimitRequest(
        name="mesh", unique_key=f"g{int(k) % 512}", hits=1, limit=10 ** 9,
        duration=600_000, behavior=Behavior.GLOBAL)
        for k in rng.zipf(ZIPF_A, size=1000)] for _ in range(4)]
    datas = _serialize_reqs(batches)

    def _drive(inst):
        inst.get_rate_limits_wire(datas[0], now_ms=NOW0)  # compile/pin
        t0 = time.perf_counter()
        outs = []
        for r in range(reps):
            outs.append(inst.get_rate_limits_wire(
                datas[r % len(datas)], now_ms=NOW0 + 1 + r))
        return reps * 1000 / (time.perf_counter() - t0), outs

    row = {"n_shards": len(jax.devices()), "batch": 1000,
           "key_domain": 512, "reconcile_interval_ms": sync_ms}
    mi = V1Instance(Config(cache_size=1 << 14, sweep_interval_ms=0,
                           global_mode="mesh",
                           behaviors=BehaviorConfig(
                               global_sync_wait_ms=sync_ms)),
                    mesh=make_mesh())
    try:
        dps_mesh, mesh_outs = _drive(mi)
        mi._mesh_reconcile_tick()  # deterministic final fold
        mge = mi._meshglobal
        mge.drain()
        s = mge.stats()
        gm = mi.global_manager
        row.update({
            "decisions_per_s": round(dps_mesh),
            "reconcile_generations": s["generation"],
            "pinned_keys": s["pinned_keys"],
            "staleness_ms": round(s["last_staleness_s"] * 1e3, 3),
            "staleness_within_interval":
                s["last_staleness_s"] * 1e3 <= sync_ms,
            "conservation_exact":
                s["folded_hits"] == s["injected_hits"],
            "injected_hits": s["injected_hits"],
            # mesh mode's whole point: nothing ever queued for gRPC
            "zero_peer_rpcs": (not gm._hits and not gm._hits_raw),
        })
        # ISSUE 11: the fitted collective cost model from this row's
        # live folds — α (launch + rendezvous) and β (per byte) per
        # (phase, ndev) bucket, the constants the hierarchical-
        # reconcile ROADMAP item prices levels with (see
        # tools/costmodel_dryrun.py for the held-out validation)
        ana = mi.analytics
        if ana is not None:
            row["cost_model"] = ana.costmodel_snapshot()
        # ISSUE 13: mesh-GLOBAL replica + accumulators in the ledger
        row["hbm"] = _hbm_block(mi)
    finally:
        mi.close()
    gi = V1Instance(Config(cache_size=1 << 14, sweep_interval_ms=0),
                    mesh=make_mesh())
    try:
        dps_grpc, grpc_outs = _drive(gi)
        row["grpc_decisions_per_s"] = round(dps_grpc)
        row["ab_identical"] = grpc_outs == mesh_outs
        row["mesh_vs_grpc"] = round(dps_mesh / max(dps_grpc, 1e-9), 3)
    finally:
        gi.close()
    if jax.default_backend() == "cpu":
        row["context"] = (
            "CPU A/B compares the mesh replica step against the "
            "IN-PROCESS sharded step (grpc mode never leaves the "
            "process here), so the ratio measures replica-table "
            "overhead only; the production win is vs per-peer gRPC "
            "round trips, which this host-only A/B cannot price. The "
            "coherence columns (conservation/staleness/zero RPCs) are "
            "the acceptance signal")
    return {"12_mesh_global": row}


def _sec_tiered():
    """Tiered key store (ISSUE 10): seeded skewed traffic whose key
    domain dwarfs a 4K-row device cap, served through the host cold
    tier and A/B'd byte-for-byte against an UNCAPPED single-tier
    oracle.  The verdict columns are the acceptance criteria: zero
    error rows, exact conservation summed across BOTH tiers, and
    bit-identical decisions; the capacity story (cold keys, hot-tier
    hit rate, migration counters) rides in the same row."""
    import jax

    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.types import RateLimitRequest

    nkeys = 20_000 if FAST else 1_000_000
    rng = np.random.default_rng(13)
    # one full pass over the domain guarantees nkeys DISTINCT keys; a
    # zipf-hot overlay gives a band of keys the rank to clear admission
    stream = np.concatenate([
        rng.permutation(nkeys),
        (rng.zipf(ZIPF_A, size=nkeys // 5) - 1) % nkeys])
    B = 1000
    pad = (-len(stream)) % B
    if pad:
        stream = np.concatenate([stream, stream[:pad]])
    datas = _serialize_reqs(
        [[RateLimitRequest(name="tier", unique_key=f"t{int(k)}", hits=1,
                           limit=10 ** 9, duration=86_400_000)
          for k in stream[base:base + B]]
         for base in range(0, len(stream), B)])
    sent = len(stream)

    def _drive(inst):
        inst.get_rate_limits_wire(datas[0], now_ms=NOW0)  # compile
        t0 = time.perf_counter()
        outs = [inst.get_rate_limits_wire(d, now_ms=NOW0 + 1)
                for d in datas]
        return sent / (time.perf_counter() - t0), outs

    def _debits(inst) -> int:
        arrays = inst.engine.snapshot()
        total = int((10 ** 9 - arrays["remaining"]).sum())
        if inst._tier is not None:
            cold = inst._tier.snapshot_arrays()
            if cold is not None:
                total += int((10 ** 9 - cold["remaining"]).sum())
        return total

    row = {"n_shards": len(jax.devices()), "key_domain": nkeys,
           "requests": sent + B, "device_cap_rows": 4096}
    ti = V1Instance(Config(cache_size=4096, cache_autogrow_max=4096,
                           tier_cold=True, tier_promote_threshold=4,
                           sweep_interval_ms=0),
                    mesh=make_mesh())
    try:
        dps_tier, tier_outs = _drive(ti)
        st = ti._tier.stats()
        # the warm-up batch's debits land in the same tables, so the
        # conservation target includes it
        row.update({
            "decisions_per_s": round(dps_tier),
            "error_rows": _count_error_rows(tier_outs),
            "conservation_exact": _debits(ti) == sent + B,
            "cold_keys": st["cold_keys"],
            "cold_served": st["cold_served"],
            "hot_hit_rate": round(1 - st["cold_served"]
                                  / max(sent + B, 1), 4),
            "promotions": st["promotions"],
            "demotions": st["demotions"],
            "migrations_aborted": st["migrations_aborted"],
            "cold_store_native": st["native"],
            # ISSUE 13: hot table + host cold tier, one ledger block
            "hbm": _hbm_block(ti),
        })
    finally:
        ti.close()
    # "uncapped" still needs placement headroom: at ~0.5 load an 8-probe
    # window can clog (~0.3% of 1M keys), and an oracle error row would
    # read as a tier A/B failure — autogrow keeps the oracle exact
    ocap = 1 << (2 * nkeys - 1).bit_length()
    oi = V1Instance(Config(cache_size=ocap, cache_autogrow_max=ocap * 8,
                           sweep_interval_ms=0),
                    mesh=make_mesh())
    try:
        dps_oracle, oracle_outs = _drive(oi)
        row["oracle_decisions_per_s"] = round(dps_oracle)
        row["oracle_error_rows"] = _count_error_rows(oracle_outs)
        row["ab_identical"] = tier_outs == oracle_outs
        row["tier_vs_uncapped"] = round(
            dps_tier / max(dps_oracle, 1e-9), 3)
    finally:
        oi.close()
    return {"13_tiered_store": row}


def _count_error_rows(outs) -> int:
    from gubernator_tpu.proto import gubernator_pb2 as pb

    n = 0
    for data in outs:
        resp = pb.GetRateLimitsResp.FromString(data)
        n += sum(1 for r in resp.responses if r.error)
    return n


def _scenario_ab(inst, reqs, pairs=9, reps=150) -> dict:
    """ISSUE 16 acceptance: the scenario lab's only service-path cost
    is its JudgeTap — ``observe()`` is an O(1) retain under a lock;
    digesting/ledgers are deferred to settle-time ``finalize()``.
    Measured as interleaved pairs of the same object-lane call with
    the tap *on* (call + observe) and *off* (plain call), alternating
    order per pair, < 3% budget.  Two departures from the
    ``_tenant_ab``/``_tracing_ab`` template, both noise armor: the
    instance under the A/B runs the synchronous OracleEngine lane
    (the oracle call is strictly FASTER than the real service call,
    so a tap cost measured as a fraction of it is an UPPER bound on
    the true service-path overhead), and the estimator is the floor
    ratio — best rate per side across all pairs — because host noise
    is one-sided (a spike only ever slows a sample) while a real
    systematic tap cost slows EVERY sample, the floor included."""
    from gubernator_tpu.scenarios import NOW0 as S_NOW0
    from gubernator_tpu.scenarios import JudgeTap

    def _measure(which):
        judge = JudgeTap(delim="/")
        t0 = time.perf_counter()
        for r in range(reps):
            resps = inst.get_rate_limits(reqs, now_ms=S_NOW0 + r)
            if which == "on":
                judge.observe(reqs, resps, S_NOW0 + r)
        return reps / (time.perf_counter() - t0)

    try:
        r_on, r_off = [], []
        for pair in range(pairs + 1):
            order = ("off", "on") if pair % 2 else ("on", "off")
            got = {w: _measure(w) for w in order}
            if pair == 0:
                continue  # warmup pair, untimed
            r_on.append(got["on"])
            r_off.append(got["off"])
        overhead = (max(r_off) / max(r_on) - 1) * 100
        row = {"overhead_pct": round(overhead, 2),
               "overhead_ok": bool(overhead < 3.0),
               "on_calls_per_s": round(max(r_on), 1),
               "off_calls_per_s": round(max(r_off), 1),
               "pairs": pairs, "reps": reps, "rows": len(reqs)}
        if not row["overhead_ok"]:
            row["warning"] = ("judge tap measured above its <3% budget "
                              "on this run; single-host noise — re-run "
                              "before acting on it")
        return row
    except Exception as e:  # noqa: BLE001 - diagnostics only
        return {"error": (str(e) or repr(e))[:200]}


def _sec_scenarios():
    """Scenario lab (ISSUE 16): run the committed spec library in fast
    mode — every stack class, every oracle — and record per-scenario
    verdicts plus the judge-tap service-path A/B.  A scenario added to
    ``scenarios/`` shows up in the next BENCH round (and ``make
    bench-diff``) with no extra wiring."""
    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.scenarios import load_library, run_scenarios
    from gubernator_tpu.types import RateLimitRequest

    doc = run_scenarios(load_library(), fast=True)
    cells = {}
    for name, r in doc["scenarios"].items():
        cell = {"ok": r["ok"], "stack": r["stack"],
                "requests": r["requests"],
                "admitted_hits": r["admitted_hits"],
                "over_limit": r["over_limit"],
                "error_rows": r["error_rows"],
                "decision_digest": r["decision_digest"][:16],
                "oracle_ok": {k: v["ok"]
                              for k, v in r["oracles"].items()}}
        if "jain_index" in r:
            cell["jain_index"] = r["jain_index"]
        cells[name] = cell
    row = {"count": doc["count"], "all_ok": doc["all_ok"],
           "scenarios": cells}
    from gubernator_tpu.oracle import OracleEngine
    inst = V1Instance(Config(cache_size=1 << 12, sweep_interval_ms=0),
                      engine=OracleEngine())
    try:
        rng = np.random.default_rng(11)
        reqs = [RateLimitRequest(name="scnab", unique_key=f"k{int(k)}",
                                 hits=1, limit=10 ** 6,
                                 duration=86_400_000)
                for k in rng.integers(0, 64, size=128)]
        inst.get_rate_limits(reqs, now_ms=NOW0)  # warm the wave path
        row["runner_ab"] = _scenario_ab(
            inst, reqs, pairs=3 if FAST else 9,
            reps=20 if FAST else 150)
    finally:
        inst.close()
    return {"15_scenarios": row}


def _audit_ab(inst, datas, pairs=9, reps=60) -> dict:
    """ISSUE 19 acceptance: the conservation audit tap must cost < 1%
    on the service path.  Interleaved pairs of the same GLOBAL wire
    call with the tap attached (``gm.audit`` is an AuditTap) and
    detached (None darkens every tap site), alternating order per
    pair, floor-ratio estimator (the ``_scenario_ab`` noise armor —
    the budget is tight enough that median-of-ratios jitter on a
    shared host would dominate the verdict)."""
    from gubernator_tpu.fleet import AuditTap

    gm = inst._ensure_global_manager()
    old = gm.audit

    def _measure(which):
        gm.audit = AuditTap() if which == "on" else None
        t0 = time.perf_counter()
        for r in range(reps):
            inst.get_rate_limits_wire(datas[r % len(datas)],
                                      now_ms=NOW0 + r)
        return reps / (time.perf_counter() - t0)

    try:
        r_on, r_off = [], []
        for pair in range(pairs + 1):
            order = ("off", "on") if pair % 2 else ("on", "off")
            got = {w: _measure(w) for w in order}
            if pair == 0:
                continue  # warmup pair, untimed
            r_on.append(got["on"])
            r_off.append(got["off"])
        overhead = (max(r_off) / max(r_on) - 1) * 100
        row = {"overhead_pct": round(overhead, 2),
               "overhead_ok": bool(overhead < 1.0),
               "on_calls_per_s": round(max(r_on), 1),
               "off_calls_per_s": round(max(r_off), 1),
               "pairs": pairs, "reps": reps}
        if not row["overhead_ok"]:
            row["warning"] = ("audit tap measured above its <1% budget "
                              "on this run; single-host noise — re-run "
                              "before acting on it")
        return row
    except Exception as e:  # noqa: BLE001 - diagnostics only
        return {"error": (str(e) or repr(e))[:200]}
    finally:
        gm.audit = old


def _sec_fleet():
    """Fleet watchtower (ISSUE 19): the audit-tap A/B on the service
    path (< 1% budget) plus the fleet-merge wall time at 3 daemons —
    fetch every daemon's debug endpoints over HTTP and time ONLY the
    exact folds (fleet.py), the cost a control plane's fleet tick
    would pay per sweep."""
    import urllib.request

    from gubernator_tpu import cluster as cluster_mod
    from gubernator_tpu import fleet
    from gubernator_tpu.config import BehaviorConfig, Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.types import Behavior, RateLimitRequest

    row = {}
    rng = np.random.default_rng(7)
    reqs = [[RateLimitRequest(name="fleetab", unique_key=f"k{int(k)}",
                              hits=1, limit=10 ** 6, duration=86_400_000,
                              behavior=Behavior.GLOBAL)
             for k in rng.zipf(ZIPF_A, size=1000) % 100_000]
            for _ in range(4)]
    datas = _serialize_reqs(reqs)
    inst = V1Instance(Config(cache_size=1 << 15, sweep_interval_ms=0))
    try:
        inst.get_rate_limits_wire(datas[0], now_ms=NOW0)  # warm
        row["audit_ab"] = _audit_ab(
            inst, datas, pairs=3 if FAST else 9,
            reps=10 if FAST else 60)
    finally:
        inst.close()

    c = cluster_mod.start(3, behaviors=BehaviorConfig(
        global_sync_wait_ms=50), cache_size=1 << 12)
    try:
        for i in range(3):
            ci = c.instance_at(i)
            ci.get_rate_limits(
                [RateLimitRequest(name="fleet", unique_key=f"m{j}",
                                  hits=1, limit=10 ** 6, duration=86_400_000,
                                  behavior=Behavior.GLOBAL)
                 for j in range(64)], now_ms=NOW0)
            ana = ci.analytics
            if ana is not None:
                ana.flush(timeout=5.0)
        # settle the flush discipline so the timed merge measures a
        # conserved steady state, not a mid-flush snapshot
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            insts = [c.instance_at(i) for i in range(3)]
            for ci in insts:
                if ci.global_manager is not None:
                    ci.global_manager.poke()
            time.sleep(0.1)
            if all(ci.audit_doc()["conserved"] for ci in insts):
                break

        def fetch(path):
            docs = []
            for i in range(3):
                url = c.http_address(i) + path
                with urllib.request.urlopen(url, timeout=5.0) as f:
                    docs.append(json.loads(f.read()))
            return docs

        raw = {p: fetch(p) for p in ("/debug/audit", "/debug/topkeys",
                                     "/debug/tenants", "/debug/slo",
                                     "/debug/memory")}
        t0 = time.perf_counter()
        fold = fleet.fold_audits(raw["/debug/audit"])
        fleet.ring_verdict(raw["/debug/audit"])
        fleet.merge_topkeys(raw["/debug/topkeys"])
        tns = fleet.merge_tenants(raw["/debug/tenants"])
        fleet.merge_slo(raw["/debug/slo"])
        fleet.merge_memory(raw["/debug/memory"])
        wall = (time.perf_counter() - t0) * 1000
        row["fleet_merge_wall_ms"] = round(wall, 3)
        row["merge"] = {"daemons": 3,
                        "drift": fold["drift"],
                        "conserved_ok": bool(fold["conserved"]),
                        "tenants_sum_ok": bool(tns["conserved"])}
    except Exception as e:  # noqa: BLE001 - diagnostics only
        row["merge"] = {"error": (str(e) or repr(e))[:200]}
    finally:
        c.stop()
    return {"16_fleet": row}


#: section name → (callable, result row keys for skip/error reporting)
_SECTIONS = {
    "lat_client": (_sec_lat_client,
                   ["client_batch_p50_ms", "client_batch_p99_ms"]),
    "scan": (_sec_scan, ["device_scan_decisions_per_s"]),
    "cfg12": (_sec_cfg12, ["1_single_key_smoke", "2_leaky_1k_keys"]),
    "cfg4": (_sec_cfg4, ["4_global_sharded"]),
    "svc": (_sec_svc, ["6_service_path", "8_peer_path"]),
    "cluster": (_sec_cluster, ["9_clustered_service"]),
    "group": (_sec_group, ["10_reuseport_group"]),
    "cfg5": (_sec_cfg5, ["5_gregorian_churn"]),
    "pallas": (_sec_pallas, ["11_pallas_serving"]),
    "mesh": (_sec_mesh, ["12_mesh_global"]),
    "tiered": (_sec_tiered, ["13_tiered_store"]),
    "scenarios": (_sec_scenarios, ["15_scenarios"]),
    "fleet": (_sec_fleet, ["16_fleet"]),
}

#: sections in reporting order (main runs `group` first: see there)
_SECTION_ORDER = ["cfg12", "cfg4", "svc", "cluster", "group", "cfg5",
                  "pallas", "mesh", "tiered", "scenarios", "fleet"]

#: sections (and inline stages of main) that raised — a non-empty list
#: makes the process exit nonzero after the rows are printed
_FAILED_SECTIONS: list = []


def _run_section(name):
    """Run one section inline.  A section that raises becomes an error
    row (so the JSON says what failed) and fails the run."""
    fn, _rows = _SECTIONS[name]
    t0 = time.perf_counter()
    try:
        rows = fn()
    except Exception as e:  # noqa: BLE001 - recorded, and exit != 0
        _FAILED_SECTIONS.append(name)
        # str() of TimeoutError/queue.Empty is "" — always keep
        # the type so the recorded row can be diagnosed
        return {"error": f"{name}: {(str(e) or repr(e))[:300]}"}
    log(f"[{name}] section done in {time.perf_counter() - t0:.1f}s")
    return rows


def _section_main() -> int:
    """GUBER_BENCH_SECTION=<name>: run that one section alone in this
    process.  Prints ONE JSON line {"section", "device", "rows"} (and
    writes the rows to GUBER_BENCH_SECTION_OUT when set); exits nonzero
    when the section raised."""
    name = os.environ["GUBER_BENCH_SECTION"]
    rows = _run_section(name)
    path = os.environ.get("GUBER_BENCH_SECTION_OUT")
    if path:
        with open(path + ".tmp", "w") as f:
            json.dump(rows, f)
        os.replace(path + ".tmp", path)
    print(json.dumps({"section": name, "device": _device_row(),
                      "rows": rows}))
    return 1 if _FAILED_SECTIONS else 0


def run_secondary_configs(step_mode, checkpoint=None, done=None):
    """BASELINE.md configs 1/2/4/5 (config 3 is the headline above)
    plus the service/cluster/group/hot rows.  Smaller rep counts —
    these document shape coverage, not the record.  ``checkpoint(out)``
    runs after each section so rows measured before a late-stage
    failure survive (see _write_partial).  ``done`` maps section name →
    rows already measured (main runs `group` before it touches JAX)."""
    # serving engines in the sections read this at construction: they
    # must run the best XLA mode — set it explicitly BOTH ways so a
    # pre-existing operator export can't make the rows measure a
    # different mode than reported.  The one exception is the dedicated
    # `pallas` section (11_pallas_serving), which forces
    # GUBER_STEP_IMPL=pallas for its own instance.
    os.environ["GUBER_STEP_DONATE"] = ("1" if step_mode == "donate"
                                      else "0")
    os.environ["GUBER_BENCH_STEP_MODE"] = step_mode
    # env beats Config in V1Instance's step_impl resolution, so an
    # operator's exported GUBER_STEP_IMPL=pallas would silently turn
    # every XLA-labeled serving row into a pallas measurement
    os.environ["GUBER_STEP_IMPL"] = "xla"
    out = {}
    for name in _SECTION_ORDER:
        _fn, row_keys = _SECTIONS[name]
        rows = (done[name] if done and name in done
                else _run_section(name))
        if "error" in rows and len(rows) == 1:
            rows = {k: {"error": rows["error"]} for k in row_keys}
        # every row names the device it was measured on; the group's
        # workers are pinned to the CPU backend whatever this process
        # holds
        device = (_device_row() if name != "group" else
                  {"platform": "cpu", "kind": "cpu (pinned workers)",
                   "count": None})
        for row in rows.values():
            if isinstance(row, dict):
                row.setdefault("device", device)
        out.update(rows)
        if checkpoint is not None:
            checkpoint(dict(out))
    return out


if __name__ == "__main__":
    sys.exit(_section_main() if os.environ.get("GUBER_BENCH_SECTION")
             else main())
