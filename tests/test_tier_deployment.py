"""The deployment ``region1-tier-100m`` (``BENCHMARK.json``, cell
``r1-churn-100m``) at its rehearsal size: a key set half again as large
as the device table on the bucket-table engine a TPU daemon runs
(``PallasServingEngine``; here in interpret mode), with the host cold
tier bound (``GUBER_TIER_COLD=1``).  A restore puts every row in exactly
one tier, a bucket's slots going to its rows in snapshot order; seeded
traffic of device-resident, cold-restored and never-seen keys over the
raw-bytes gRPC front door answers as the benchmark's own plain
token-bucket reference, which has no tiers and imports nothing of the
program; the cold store's batch put is its per-row put; the cell and its
readers are found by name."""
import json
import os
import subprocess
import sys
import time

import grpc
import numpy as np
import pytest

from benchmark import run
from benchmark.algorithms import token_bucket as tb
from benchmark.harness import plugins, traffic as tr, wire
from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.daemon import spawn_daemon
from gubernator_tpu.metrics import Metrics
from gubernator_tpu.netutil import free_port
from gubernator_tpu.ops import pallas_step as ps
from gubernator_tpu.parallel import make_mesh
from gubernator_tpu.parallel.pallas_engine import PallasServingEngine
from gubernator_tpu.tiering import ROW_COLS, TierController, _make_store

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "r1-churn-100m"
SEED = 4100000021
ROWS = 2048  # 16 buckets of 128 slots
NEW_READERS = ("tier_cold_rows_per_wave", "tier_created_keys_per_wave",
               "tier_resolve_ms", "tier_premask_ms",
               "tier_migrations_per_s", "tier_cold_keys_m",
               "tier_native_apply_share")


def _cell():
    cell = run.load_cell(CELL, rehearsal=True)
    cfg, mix = cell["config"], cell["traffic"]
    return cell, cfg, mix, cfg["populations"][mix["population"]]


def _engine(metrics=None):
    eng = PallasServingEngine(make_mesh(n=1), capacity_per_shard=ROWS,
                              batch_per_shard=128)
    eng.metrics_ref = metrics
    return eng


def _tiered_engine(metrics=None):
    eng = _engine(metrics)
    return eng, TierController(eng, metrics=metrics)


def _two_tier_snapshot(eng, tier) -> dict:
    """Both tiers' rows as one set of store.py columns, device first."""
    dev = eng.snapshot()
    live = dev["key"] != 0
    cold = tier.snapshot_arrays()
    return {f: np.concatenate([np.asarray(dev[f])[live],
                               np.asarray(cold[f])])
            for f in ("key",) + ROW_COLS}


def _by_key(cols: dict) -> dict:
    return {int(k): tuple(int(cols[f][i]) for f in ROW_COLS)
            for i, k in enumerate(cols["key"])}


# ---- (a) the restore contract on the bucket-table engine ----------------

def test_restore_puts_every_row_in_exactly_one_tier_in_snapshot_order():
    _, _, _, pop = _cell()
    pop = {**pop, "keys": ROWS * 3 // 2}  # 1.5 keys a row
    snap = tb.snapshot_columns(pop, SEED, 1_800_000_000_000)
    metrics = Metrics()
    eng, tier = _tiered_engine(metrics)
    assert eng.restore(snap) == pop["keys"]  # placed + adopted
    assert eng.dropped_rows == 0
    text = metrics.render().decode()
    assert "gubernator_restore_unplaced_rows 0.0" in text
    assert 'gubernator_phase_duration_count{phase="restore.adopt"} 1.0' \
        in text
    assert 'gubernator_phase_duration_count{phase="restore.place"} 1.0' \
        in text
    keys = snap["key"]
    on_dev, _ = eng.gather_rows(keys)
    cold = tier.resident_mask(keys)
    assert (on_dev ^ cold).all(), "a row in both tiers, or in none"
    assert tier.cold_keys() == int(cold.sum()) > 0
    # every bucket overflows at this load, and holds its FIRST 128 rows
    bucket = eng._bucket_ids(keys)
    for b in range(ROWS // ps.SLOTS):
        mine = np.flatnonzero(bucket == b)
        assert len(mine) > ps.SLOTS, b
        assert on_dev[mine[:ps.SLOTS]].all(), b
        assert cold[mine[ps.SLOTS:]].all(), b
    # snapshot → restore → snapshot: the same rows, in the same tiers
    first = _two_tier_snapshot(eng, tier)
    assert _by_key(first) == _by_key(snap)
    eng2, tier2 = _tiered_engine()
    assert eng2.restore(first) == pop["keys"]
    assert _by_key(_two_tier_snapshot(eng2, tier2)) == _by_key(first)
    assert (tier2.resident_mask(keys) == cold).all()


def test_restore_without_a_tier_counts_what_it_drops():
    """The same snapshot with no tier bound: the overflow is dropped,
    counted, and ``restore`` says how many rows it placed — what
    ``benchmark/run.py › restore_rows`` refuses a set-up on."""
    _, _, _, pop = _cell()
    pop = {**pop, "keys": ROWS * 3 // 2}
    metrics = Metrics()
    eng = _engine(metrics)
    placed = eng.restore(tb.snapshot_columns(pop, SEED, 1_800_000_000_000))
    assert placed == ROWS  # every bucket full
    assert eng.dropped_rows == pop["keys"] - ROWS
    assert (f"gubernator_restore_unplaced_rows {float(pop['keys'] - ROWS)}"
            in metrics.render().decode())


def test_a_key_that_comes_twice_is_adopted_once_with_its_last_row():
    """Rows of one key count as restored each, placed or adopted, and
    the tier holds the key's LAST row (as the device would)."""
    _, _, _, pop = _cell()
    pop = {**pop, "keys": ROWS * 3 // 2}
    snap = tb.snapshot_columns(pop, SEED, 1_800_000_000_000)
    eng, tier = _tiered_engine()
    eng.restore(snap)
    lost = int(np.flatnonzero(tier.resident_mask(snap["key"]))[0])
    twice = {f: np.concatenate([v, v[lost:lost + 1]])
             for f, v in snap.items()}
    twice["remaining"][-1] = 77
    eng, tier = _tiered_engine()
    assert eng.restore(twice) == pop["keys"] + 1
    assert tier.peek_row(int(snap["key"][lost]))["remaining"] == 77
    assert tier.cold_keys() == pop["keys"] - ROWS


def _with_out_of_domain_rows(snap: dict, at) -> dict:
    """``snap`` with a limit at rows ``at`` that the kernel's packed
    words cannot hold (>= 2^30): rows that must stay cold."""
    out = {f: np.array(v) for f, v in snap.items()}
    out["limit"][at] = ps.VALUE_BOUND + 5
    return out


def test_rows_outside_the_kernels_domain_are_adopted_not_dropped():
    """A tiered daemon's snapshot holds rows the kernel cannot serve
    (the tier answers them, ``tier_rows_admissible`` keeps them cold): a
    restore hands them to the tier, and without a tier counts them in
    ``dropped_rows`` AND the gauge."""
    _, _, _, pop = _cell()
    pop = {**pop, "keys": ROWS * 3 // 2}
    at = np.array([0, 7, 300, pop["keys"] - 1])
    snap = _with_out_of_domain_rows(
        tb.snapshot_columns(pop, SEED, 1_800_000_000_000), at)
    metrics = Metrics()
    eng, tier = _tiered_engine(metrics)
    assert eng.restore(snap) == pop["keys"]
    assert eng.dropped_rows == 0
    assert "gubernator_restore_unplaced_rows 0.0" in metrics.render().decode()
    on_dev, _ = eng.gather_rows(snap["key"])
    cold = tier.resident_mask(snap["key"])
    assert (on_dev ^ cold).all() and cold[at].all()
    assert tier.peek_row(int(snap["key"][7]))["limit"] == ps.VALUE_BOUND + 5
    first = _two_tier_snapshot(eng, tier)
    assert _by_key(first) == _by_key(snap)
    eng2, tier2 = _tiered_engine()
    assert eng2.restore(first) == pop["keys"]  # the round trip keeps them
    assert _by_key(_two_tier_snapshot(eng2, tier2)) == _by_key(first)
    # no tier: dropped, and said so
    metrics = Metrics()
    eng = _engine(metrics)
    assert eng.restore(snap) == ROWS
    assert eng.dropped_rows == pop["keys"] - ROWS
    assert (f"gubernator_restore_unplaced_rows {float(pop['keys'] - ROWS)}"
            in metrics.render().decode())


@pytest.mark.parametrize("last", ["out_of_domain", "in_domain"])
def test_a_keys_last_row_decides_its_tier(last):
    """One key, one row inside the kernel's domain and one outside it:
    the LAST one is the key's row, in one tier only, and both count."""
    _, _, _, pop = _cell()
    snap = tb.snapshot_columns({**pop, "keys": 300}, SEED,
                               1_800_000_000_000)
    twice = {f: np.concatenate([v, v[5:6]]) for f, v in snap.items()}
    twice["remaining"][-1] = 77
    twice = _with_out_of_domain_rows(
        twice, -1 if last == "out_of_domain" else 5)
    eng, tier = _tiered_engine()
    assert eng.restore(twice) == 301
    kh = snap["key"][5:6]
    on_dev, cols = eng.gather_rows(kh)
    assert on_dev[0] == (last == "in_domain")
    assert tier.resident_mask(kh)[0] == (last == "out_of_domain")
    row = tier.peek_row(int(kh[0])) if last == "out_of_domain" \
        else {f: int(cols[f][0]) for f in ROW_COLS}
    assert row["remaining"] == 77
    assert tier.cold_keys() == (last == "out_of_domain")


# ---- (b) the deployment answers as the plain reference ------------------

@pytest.mark.parametrize("native", ["1", "0"])
def test_the_tiered_deployment_answers_as_the_plain_reference(monkeypatch,
                                                              native):
    """Both lanes of the cold tier: the native store, whose rows ONE C++
    pass applies (the deployment's own), and the dict store with the
    Python loop (``GUBER_TIER_NATIVE=0``)."""
    _, cfg, mix, pop = _cell()
    for name in [k for k in os.environ if k.startswith("GUBER_")]:
        monkeypatch.delenv(name)
    for name, value in cfg["env"].items():
        monkeypatch.setenv(name, value)
    monkeypatch.setenv("GUBER_TIER_NATIVE", native)
    addr = f"127.0.0.1:{free_port()}"
    daemon = spawn_daemon(DaemonConfig(
        grpc_listen_address=addr,
        http_listen_address=f"127.0.0.1:{free_port()}", **cfg["daemon"]),
        mesh=make_mesh(n=1))  # the cell's one chip: conftest shows eight
    chan = grpc.insecure_channel(addr)
    try:
        inst = daemon.instance
        assert isinstance(inst.engine, PallasServingEngine)
        v0 = (int(time.time()) + 86_400) * 1000
        snap = tb.snapshot_columns(pop, SEED, v0)
        with inst._engine_mu:
            assert inst.engine.restore(snap) == pop["keys"]
        cold0 = inst._tier.resident_mask(snap["key"])
        # every bucket full; the daemon's own start-up request holds a slot
        assert cold0.sum() - (pop["keys"] - cfg["daemon"]["cache_size"]) \
            in (0, 1)
        ref = tb.reference(pop)
        tb.seed_reference(ref, np.arange(pop["keys"]), pop, SEED, v0)
        tpl = wire.RequestTemplate(
            name=pop["name"], hits=pop["hits"], limit=pop["limit"],
            duration=pop["duration_ms"], **tb.request_fields(pop))
        draw = plugins.load("keys", mix["keys"]["dist"]).sample
        call = chan.unary_unary(wire.METHOD)
        rng = tr.caller_rng(SEED, 0)
        cold_idx = np.flatnonzero(cold0)
        kinds = np.zeros(3, np.int64)
        over = 0
        for c in range(8):
            # 2.6 s apart: restored rows answer, expire, and re-open
            stamp = v0 + c * 2_600
            idx = np.concatenate([
                draw(rng, mix["keys"], 60, pop["keys"]),
                rng.choice(cold_idx, 25),  # restored to the host
                rng.integers(pop["keys"], mix["keys"]["space"], 15)])
            rng.shuffle(idx)
            got = wire.decode_responses(
                call(tpl.call(tr.key_id(idx, SEED), stamp), timeout=300))
            want = ref.call(idx, stamp)
            assert got["errors"] == 0, "an error string reached the client"
            for f in ("status", "limit", "remaining", "reset_time"):
                assert (got[f] == want[f]).all(), (c, f)
            over += int((want["status"] == tb.OVER).sum())
            seen = idx < pop["keys"]
            went_cold = seen & cold0[np.minimum(idx, pop["keys"] - 1)]
            kinds += (int((seen & ~went_cold).sum()), int(went_cold.sum()),
                      int((~seen).sum()))
        assert kinds.min() > 0, kinds  # device, cold-restored, never seen
        assert over > 0, "the stream has to cross the limit"
        st = inst._tier.stats()
        # (a first-seen key may find the one slot the start-up
        # request's expired row gave back at the first sweep)
        assert kinds[1] < st["cold_served"] <= kinds[1] + kinds[2]
        assert 0 < st["cold_created"] <= kinds[2]
        assert st["cold_keys"] == cold0.sum() + st["cold_created"] \
            - st["promotions"] + st["demotions"]
        m = {k: float(v) for k, v in (
            line.rsplit(" ", 1) for line in
            inst.metrics.render().decode().splitlines()
            if line and not line.startswith("#"))}
        assert m["gubernator_tier_cold_creates_total"] == st["cold_created"]
        assert m["gubernator_tier_cold_serves_total"] == st["cold_served"]
        assert st["native"] == (native == "1")
        assert m["gubernator_tier_cold_native_serves_total"] == (
            st["cold_served"] if st["native"] else 0.0)
        assert m.get("gubernator_table_full_rows_total", 0.0) == 0.0
        for p in ("tier.premask", "tier.resolve", "restore.adopt"):
            assert m[f'gubernator_phase_duration_count{{phase="{p}"}}'] > 0
        assert not any("pb2" in k and v for k, v in m.items()
                       if k.startswith("gubernator_wire_lane_requests"))
        assert m["gubernator_wire_fused_requests_total"] == 8 * 100
    finally:
        chan.close()
        daemon.close()


# ---- (c) the cold store's batch put -------------------------------------

@pytest.mark.parametrize("native", ["1", "0"])
def test_batch_put_is_the_per_row_put(monkeypatch, native):
    monkeypatch.setenv("GUBER_TIER_NATIVE", native)
    one, many = _make_store(), _make_store()
    if native == "1" and not one.native:
        pytest.skip("native cold_* primitives not built")
    assert one.native == (native == "1")
    rng = np.random.default_rng(7)
    keys = rng.integers(1, 1 << 62, 5000).astype(np.uint64)
    keys[100:200] = keys[:100]  # keys that come twice: the last row stays
    rows = rng.integers(-(1 << 62), 1 << 62, (5000, len(ROW_COLS)))
    for k, r in zip(keys[:3000].tolist(), rows[:3000].tolist()):
        one.put(k, tuple(r))
    many.put_batch(keys[:3000], rows[:3000])
    for k in keys[500:900].tolist():  # tombstones before the next batch
        assert one.pop(k) == many.pop(k)
    for k, r in zip(keys[3000:].tolist(), rows[3000:].tolist()):
        one.put(k, tuple(r))
    many.put_batch(keys[3000:], rows[3000:])
    assert len(one) == len(many) == len(set(keys.tolist())) - 400
    probe = np.concatenate([keys, np.array([3, 5], np.uint64)])
    assert (one.contains_batch(probe) == many.contains_batch(probe)).all()
    snaps = []
    for store in (one, many):
        k, r = store.snapshot()
        snaps.append({int(a): tuple(map(int, b)) for a, b in zip(k, r)})
    assert snaps[0] == snaps[1]
    assert many.get(int(keys[0])) == tuple(rows[100].tolist())
    many.put_batch(keys[:0], rows[:0])  # nothing is fine
    assert len(many) == len(one)


def test_a_batch_put_that_cannot_grow_is_a_memory_error():
    """``cold_put_batch`` grows the table with the GIL released: an
    allocation that fails is a MemoryError with the store as it was,
    not a C++ exception that ends the daemon.  (A process of its own:
    the address-space limit must not outlive the test.)"""
    code = """
import resource, numpy as np
from gubernator_tpu.ops import _native as m
h = m.cold_new(0)
assert m.cold_put_batch(h, np.arange(1, 101, dtype="<u8"),
                        np.ones((100, 8), "<i8")) == 100
n = 2_000_000  # the grow: 2^22 slots x 73 B = 306 MB
keys, rows = np.arange(1, n + 1, dtype="<u8"), np.zeros((n, 8), "<i8")
vm = int(open("/proc/self/statm").read().split()[0]) * 4096
resource.setrlimit(resource.RLIMIT_AS, (vm + (96 << 20),) * 2)
try:
    m.cold_put_batch(h, keys, rows)
except MemoryError:
    assert m.cold_get(h, 5)[:8] == (1).to_bytes(8, "little")
    assert m.cold_get(h, 5000) is None
    print("MemoryError")
"""
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO, timeout=120)
    assert p.stdout.strip() == "MemoryError", (p.returncode, p.stderr[-800:])


def test_adopt_rows_takes_columns_and_indices(monkeypatch):
    _, _, _, pop = _cell()
    snap = tb.snapshot_columns({**pop, "keys": 500}, SEED, 1_800_000_000_000)
    eng, tier = _tiered_engine()
    idx = np.arange(0, 500, 3)
    assert tier.adopt_rows(snap, idx) == len(idx)
    assert tier.cold_keys() == len(idx)
    for i in idx[:20].tolist():
        row = tier.peek_row(int(snap["key"][i]))
        assert row == {f: int(snap[f][i]) for f in ROW_COLS}
    assert tier.adopt_rows(snap, []) == 0


# ---- (d) found by name --------------------------------------------------

def test_the_deployment_and_its_cell_are_found_by_name():
    cell, cfg, mix, pop = _cell()
    full = run.load_cell(CELL, rehearsal=False)
    fcfg = full["config"]
    fpop = fcfg["populations"][full["traffic"]["population"]]
    assert full["chips"] == 1 and fcfg["engine"] == "pallas-fused"
    assert fcfg["env"] == {"GUBER_TIER_COLD": "1"}
    assert fcfg["key_space"] == full["traffic"]["keys"]["space"] == 10 ** 8
    # the source's 1.49 keys a row, at both sizes, in whole buckets
    for c, p in ((fcfg, fpop), (cfg, pop)):
        rows = c["daemon"]["cache_size"]
        assert rows % ps.SLOTS == 0 and p["keys"] / rows >= 1.4
    assert abs(fpop["keys"] / fcfg["daemon"]["cache_size"]
               - 10 ** 8 / 2 ** 26) < 0.01
    assert plugins.algorithm(fpop).__file__ == tb.__file__  # tier-free
    assert {m["name"] for m in cell["end_to_end"]} == {
        "decisions_per_s", "call_p50_ms", "setup_s"}
    mine = {m["name"] for m in cell["per_layer"]}
    assert set(NEW_READERS) <= mine
    assert {"fused_ingest_share", "wave_identity_route_share",
            "wave_native_route_share", "sweep_ms",
            "device_idle_share", "hbm_peak_gb"} <= mine
    # the kernel's two readers divide by the rows of ONE launch a
    # dispatcher wave; a tiered wave is two (PERF.md 7): not here
    assert not {"kernel_ns_per_row", "decide_kernel_roofline"} & mine
    manifest = run.load_json(REPO, "BENCHMARK.json")
    for m in manifest["per_layer"]:
        if m["name"] in NEW_READERS:
            # the tier's cells, in the order they were added (ISSUE 46
            # appended the waking-tenant cell to every list this one is on)
            assert m["workloads"] == [CELL, "r1-drift-100m"]
            assert m["layer"] == "cold tier"
    assert next(m for m in manifest["per_layer"]
                if m["name"] == "tier_launches_per_wave") == {
        "name": "tier_launches_per_wave", "unit": "launches",
        "better": "lower", "source": "program_counter", "layer": "engine",
        "moves": "decisions_per_s", "workloads": [CELL, "r1-drift-100m"]}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_reads_nothing_without_its_series(name):
    """On the scrapes of a program that lacks what this PR adds (the
    parent's, under these benchmark files) a reader returns None and
    does not raise."""
    read = plugins.load("layer_metrics", name).read
    waves = {"gubernator_dispatcher_wave_size_count": 9.0,
             "gubernator_dispatcher_wave_duration_count": 9.0}
    ctx = {"m0": dict(waves), "m1": {k: 2 * v for k, v in waves.items()},
           "seconds": 4.0}
    assert read(ctx) is None


def test_the_new_readers_on_canned_scrapes():
    m0 = {"gubernator_dispatcher_wave_size_count": 10.0,
          "gubernator_dispatcher_wave_duration_count": 10.0,
          "gubernator_tier_cold_serves_total": 100.0,
          "gubernator_tier_cold_native_serves_total": 100.0,
          "gubernator_tier_cold_creates_total": 50.0,
          "gubernator_tier_promotions_total": 1.0,
          "gubernator_tier_demotions_total": 1.0,
          "gubernator_tier_cold_keys": 7.0e6,
          'gubernator_phase_duration_sum{phase="tier.resolve"}': 1.0,
          'gubernator_phase_duration_count{phase="tier.resolve"}': 20.0,
          'gubernator_phase_duration_sum{phase="tier.premask"}': 0.1,
          'gubernator_phase_duration_count{phase="tier.premask"}': 30.0}
    m1 = {**{k: 2 * v for k, v in m0.items()},
          "gubernator_dispatcher_wave_size_count": 20.0,
          "gubernator_dispatcher_wave_duration_count": 20.0,
          "gubernator_tier_cold_serves_total": 12_100.0,
          "gubernator_tier_cold_native_serves_total": 9_100.0,
          "gubernator_tier_cold_creates_total": 10_550.0,
          "gubernator_tier_promotions_total": 4.0,
          "gubernator_tier_demotions_total": 2.0,
          "gubernator_tier_cold_keys": 9.5e6}
    ctx = {"m0": m0, "m1": m1, "seconds": 4.0}
    got = {n: plugins.load("layer_metrics", n).read(ctx)
           for n in NEW_READERS}
    assert got == {"tier_cold_rows_per_wave": 1200.0,
                   "tier_created_keys_per_wave": 1050.0,
                   "tier_resolve_ms": pytest.approx(100.0),
                   "tier_premask_ms": pytest.approx(10.0),
                   "tier_migrations_per_s": 1.0,
                   "tier_cold_keys_m": 9.5,
                   "tier_native_apply_share": 75.0}


@pytest.mark.parametrize("routes, want", [
    ({}, None),  # a program without the counter
    ({"sorted": (30.0, 70.0)}, 2.0),  # ISSUE 43: launch + ONE re-dispatch
    ({"sorted": (30.0, 110.0)}, 4.0),  # the parent: three re-dispatches
    ({"identity": (5.0, 20.0), "sorted": (0.0, 5.0)}, 1.0),
])
def test_launches_per_wave_sums_the_routes(routes, want):
    """``tier_launches_per_wave``: the device waves of every route ÷ the
    dispatcher's waves, between the window's scrapes."""
    m0 = {"gubernator_dispatcher_wave_size_count": 10.0}
    m1 = {"gubernator_dispatcher_wave_size_count": 30.0}
    for route, (a, b) in routes.items():
        m0[f'gubernator_wave_route_total{{route="{route}"}}'] = a
        m1[f'gubernator_wave_route_total{{route="{route}"}}'] = b
    read = plugins.load("layer_metrics", "tier_launches_per_wave").read
    assert read({"m0": m0, "m1": m1, "seconds": 4.0}) == want


@pytest.fixture(scope="module")
def traced_rehearsal():
    """The cell's CPU rehearsal under ``--trace 1``, run once: (the
    process, its result line)."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "3",
         "--trace", "1", "--cpu-rehearsal"],
        capture_output=True, text=True, cwd=REPO, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1
    return p, json.loads(lines[0])


def test_the_traced_rehearsal_prints_every_metric_listed_for_the_cell(
        traced_rehearsal):
    """The check PR 45 failed in this cell (ISSUE 46): ``benchmark/run.py``
    LEAVES OUT a per-layer metric whose reader returns ``None``, and the
    driver then finds a listed metric missing — so every metric
    ``BENCHMARK.json`` lists for this cell, or lists for none, has to
    read something here, where a window may hold no migration at all.
    (A ``device_trace`` metric needs a device plane, which the CPU
    rehearsal's profile has none of.)  The waking-tenant cell's twin:
    ``tests/test_tier_migration.py``."""
    _, line = traced_rehearsal
    listed = {m["name"] for m in run.load_cell(CELL, True)["per_layer"]
              if m["source"] != "device_trace"}
    assert listed - set(line["metrics"]) == set()
    # the two readers of the migration pass are the other tier cell's
    assert not {"tier_migrate_ms", "tier_rows_per_migration"} & (
        listed | set(line["metrics"]))


def test_the_cpu_rehearsal_of_the_cell_is_correct_and_reads_the_tier(
        traced_rehearsal):
    p, line = traced_rehearsal
    assert line["correct"] is True, p.stderr[-3000:]
    assert all(got <= limit for got, which, limit
               in line["checks"].values() if which == "at most")
    assert "3000 of 3000 rows restored" in p.stderr
    metrics = line["metrics"]
    assert set(NEW_READERS) <= set(metrics)
    assert 0 < metrics["tier_cold_rows_per_wave"]["value"] \
        < metrics["rows_per_wave"]["value"]
    assert metrics["tier_created_keys_per_wave"]["value"] > 0
    # the rehearsal's daemon runs the build it was started from: every
    # cold row went through the C++ pass
    assert metrics["tier_native_apply_share"]["value"] == 100.0
    assert metrics["tier_cold_keys_m"]["value"] * 1e6 > 3000 - 2048
    assert metrics["fused_ingest_share"]["value"] == 100.0
    assert metrics["wave_identity_route_share"]["value"] == 0.0
    # the wave and at most ONE re-dispatch of its unanswered rows (a
    # wave in flight at a scrape is split between the counters: ~40
    # waves a window here; the parent reads 3.65)
    assert 1.0 < metrics["tier_launches_per_wave"]["value"] < 2.5


@pytest.mark.parametrize("fault", ["forget", "fork"])
def test_a_fault_of_the_tier_is_not_correct(fault):
    """``tools/tier_fault_control.py``: the cell's rehearsal on a program
    whose cold store forgets (or forks) one held row in two must end
    NOT correct — the checks see the tier's own guarantee, not only the
    arithmetic (at the cell's size, on the chip: PERF.md 6, PR 41)."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tier_fault_control.py"),
         fault, "2", "--", "--workload", CELL, "--seed", str(SEED + 2),
         "--seconds", "3", "--trace", "0", "--cpu-rehearsal"],
        capture_output=True, text=True, cwd=REPO, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, p.stderr[-3000:]
    checks = line["checks"]
    # (the replay is one caller's, so the same every run; how many of
    # the window's few answers at this size meet a faulted row is not)
    assert checks["replay_mismatches"][0] > 0, checks
    assert checks["responses_with_error"][0] == 0
    assert f"tier fault {fault!r}" in p.stderr

