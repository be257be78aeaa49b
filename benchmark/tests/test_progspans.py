"""The idle attribution over the program's own phases
(``harness/progspans.py``), on a small hand-made event table: exclusive
groups that sum to the idle total, and a wave phase in a caller thread
beating ``worker.wait``; and the counter readers on two hand-made
scrapes."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import progspans, tracered  # noqa: E402

DEV = tracered.DEVICE_PLANE + "0"
OPS = tracered.OPS_LINE
HOST = "/host:CPU"


def table():
    """One device, window [0, 1000]: ops at 0–100, 400–450, 900–1000, so
    idle = [100, 400] + [450, 900] = 750.  The worker (line w):
    wave.route 50–200, wave.dispatch 200–300, worker.wait 300–520,
    wave.sync 520–700, wave.end 700–720, worker.wait 720–1000.  A caller
    thread (line c) holds lock.engine 320–380 while the worker waits;
    the benchmark's own outside span is not a program phase."""
    return [
        [DEV, OPS, "%fusion.1 = f32[] fusion()", 0.0, 100.0],
        [DEV, OPS, "%fusion.1 = f32[] fusion()", 400.0, 50.0],
        [DEV, OPS, "%fusion.1 = f32[] fusion()", 900.0, 100.0],
        [DEV, "XLA Modules", "jit_step", 0.0, 1000.0],
        [HOST, "w", "wave.route", 50.0, 150.0],
        [HOST, "w", "wave.dispatch", 200.0, 100.0],
        [HOST, "w", "worker.wait", 300.0, 220.0],
        [HOST, "w", "wave.sync", 520.0, 180.0],
        [HOST, "w", "wave.end", 700.0, 20.0],
        [HOST, "w", "worker.wait", 720.0, 280.0],
        [HOST, "c", "lock.engine", 320.0, 60.0],
        [HOST, "c", "dispatcher.launch", 0.0, 1000.0],
        [HOST, "c", "pack", 0.0, 1000.0],  # coarse: in no group
    ]


def test_groups_are_exclusive_and_sum_to_the_idle_total():
    got = progspans.idle_by_group(table())
    assert got["idle"] * 1e9 == 750.0
    # launch side: route/dispatch 100–300, and the caller's lock wait
    # 320–380 — which beats the worker.wait open at the same time
    assert got["launch_side"] * 1e9 == 200.0 + 60.0
    # sync side: 520–720 of idle
    assert got["sync_side"] * 1e9 == 200.0
    # no work: worker.wait 300–400 less the lock wait, 450–520, 720–900
    assert got["no_work"] * 1e9 == (100.0 - 60.0) + 70.0 + 180.0
    assert got["unattributed"] == 0.0
    parts = sum(got[g] for g in ("launch_side", "sync_side", "no_work",
                                 "unattributed"))
    assert abs(parts - got["idle"]) < 1e-15


def test_idle_with_no_phase_open_is_unattributed():
    rows = [r for r in table() if r[2] != "worker.wait"]
    got = progspans.idle_by_group(rows)
    assert got["no_work"] == 0.0
    assert got["unattributed"] * 1e9 == 40.0 + 70.0 + 180.0
    shares = {g: 100.0 * got[g] / got["idle"]
              for g in ("launch_side", "sync_side", "no_work",
                        "unattributed")}
    assert abs(sum(shares.values()) - 100.0) < 1e-9


def test_a_program_without_phases_reads_nothing():
    """The parent commit: its trace holds the benchmark's spans only,
    its scrapes no phase family — every reader returns None."""
    rows = [r for r in table()
            if r[0] == DEV or r[2] == "dispatcher.launch"]
    assert progspans.idle_by_group(rows) is None
    assert progspans.idle_by_group([r for r in table()
                                    if r[0] != DEV]) is None
    ctx = {"m0": {}, "m1": {"gubernator_dispatcher_wave_duration_count":
                            10.0}, "seconds": 10.0}
    assert progspans.ms_per_wave(ctx, "wave.begin") is None
    assert progspans.ms_per_sample(ctx, "route.keys") is None
    assert progspans.share_of_worker(ctx, "worker.wait") is None
    assert progspans.wait_share(ctx, "route.") is None


def test_counter_readers():
    d = progspans.DURATION
    m0 = {f'{d}_sum{{phase="wave.begin"}}': 1.0,
          f'{d}_count{{phase="wave.begin"}}': 10.0,
          "gubernator_dispatcher_wave_duration_count": 10.0}
    m1 = {f'{d}_sum{{phase="wave.begin"}}': 1.5,
          f'{d}_count{{phase="wave.begin"}}': 60.0,
          f'{d}_sum{{phase="wave.resolve"}}': 0.25,
          f'{d}_count{{phase="wave.resolve"}}': 50.0,
          f'{d}_sum{{phase="resolve"}}': 9.0,   # the coarse phase: other
          f'{d}_count{{phase="resolve"}}': 50.0,
          f'{d}_sum{{phase="lock.engine"}}': 0.1,
          f'{d}_count{{phase="lock.engine"}}': 50.0,
          f'{d}_sum{{phase="lock.xla_exec"}}': 0.2,
          f'{d}_count{{phase="lock.xla_exec"}}': 100.0,
          f'{d}_sum{{phase="route.keys"}}': 4.0,
          f'{d}_count{{phase="route.keys"}}': 8.0,
          f'{d}_sum{{phase="route.pack"}}': 1.0,
          f'{d}_count{{phase="route.pack"}}': 8.0,
          f'{progspans.CPU_SECONDS}{{phase="route.keys"}}': 0.3,
          f'{progspans.CPU_SECONDS}{{phase="route.pack"}}': 0.2,
          f'{progspans.CPU_WALL_SECONDS}{{phase="route.keys"}}': 4.0,
          f'{progspans.CPU_WALL_SECONDS}{{phase="route.pack"}}': 1.0,
          # a sampled wave phase: CPU against its OWN wall, not all
          f'{progspans.CPU_SECONDS}{{phase="pack"}}': 0.02,
          f'{progspans.CPU_WALL_SECONDS}{{phase="pack"}}': 0.1,
          f'{d}_sum{{phase="pack"}}': 1.6,
          f'{d}_count{{phase="pack"}}': 50.0,
          f'{d}_sum{{phase="worker.wait"}}': 2.0,
          f'{d}_count{{phase="worker.wait"}}': 3.0,
          "gubernator_dispatcher_wave_duration_count": 60.0}
    ctx = {"m0": m0, "m1": m1, "seconds": 10.0}
    assert progspans.ms_per_wave(ctx, "wave.begin") == 10.0
    assert progspans.ms_per_wave(ctx, "wave.resolve") == 5.0
    # both locks, per WAVE whatever their own sample counts
    assert abs(progspans.ms_per_wave(ctx, "lock.") - 6.0) < 1e-9
    assert progspans.ms_per_sample(ctx, "route.keys") == 500.0
    # the worker's own phases are the whole: 0.5 + 0.25 + 0.3 + 2.0 s
    assert abs(progspans.share_of_worker(ctx, "worker.wait")
               - 100.0 * 2.0 / 3.05) < 1e-9
    assert abs(progspans.wait_share(ctx, "route.") - 90.0) < 1e-9
    assert abs(progspans.wait_share(ctx, "pack", "resolve") - 80.0) < 1e-9
