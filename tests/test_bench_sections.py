"""bench.py's launcher contract, pinned on CPU.

Sections run inline in the one process that holds the chip; a single
section runs alone via ``GUBER_BENCH_SECTION=<name> python bench.py``
and prints ONE JSON line naming the device its rows were measured on;
a section that raises leaves an error row AND a nonzero exit code.
bench.py starts no process once it has touched JAX and never
substitutes a CPU run.  The remaining tests pin row schemas by calling
the A/B helpers directly on small instances."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run_section(name, tmp_path, extra_env=None, timeout=300):
    out = str(tmp_path / f"sec_{name}.json")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               GUBER_BENCH_SECTION=name,
               GUBER_BENCH_SECTION_OUT=out,
               GUBER_BENCH_FAST="1")
    env.update(extra_env or {})
    return out, subprocess.run([sys.executable, BENCH], env=env, cwd=REPO,
                               timeout=timeout, stdout=subprocess.PIPE,
                               stderr=subprocess.PIPE)


def test_single_section_protocol(tmp_path):
    out, r = _run_section("cfg12", tmp_path)
    assert r.returncode == 0, r.stderr.decode()[-500:]
    line = json.loads(r.stdout.decode().strip().splitlines()[-1])
    assert line["section"] == "cfg12"
    # every row names the device it was measured on, as JAX reports it
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["kind"] and line["device"]["count"] >= 1
    rows = line["rows"]
    assert set(rows) == {"1_single_key_smoke", "2_leaky_1k_keys"}
    for v in rows.values():
        assert v.get("decisions_per_s", 0) > 0, rows
    with open(out) as f:
        assert json.load(f) == rows


def test_a_section_that_raises_fails_the_process(tmp_path):
    """An error row is recorded (the JSON says what failed) and the
    exit code is nonzero — never a quiet zero-valued row."""
    code = (
        "import sys, bench\n"
        "def boom():\n"
        "    raise RuntimeError('section on fire')\n"
        "bench._SECTIONS['boom'] = (boom, ['99_boom'])\n"
        "sys.exit(bench._section_main())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", GUBER_BENCH_SECTION="boom")
    env.pop("GUBER_BENCH_SECTION_OUT", None)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       timeout=120, stdout=subprocess.PIPE,
                       stderr=subprocess.PIPE)
    assert r.returncode == 1, r.stderr.decode()[-500:]
    line = json.loads(r.stdout.decode().strip().splitlines()[-1])
    assert "section on fire" in line["rows"]["error"]


def test_bench_starts_no_process_after_touching_jax_and_has_no_cpu_stand_in():
    """Static pin of the launcher contract: the only process spawns in
    bench.py are the SO_REUSEPORT group's (CPU-pinned workers, via
    cluster.start_subprocess_group), and main() runs that section
    before its first jax import."""
    src = open(BENCH).read()
    assert "subprocess.run" not in src and "Popen" not in src
    main_src = src[src.index("def main()"):src.index("def _device_row")]
    assert main_src.index('_run_section("group")') \
        < main_src.index("import jax")
    for gone in ("_watchdog_main", "_device_probe", "clear_" "backends",
                 "GUBER_BENCH_FAST\": \"1\""):
        assert gone not in src, gone


def test_tracing_ab_block_schema():
    """The 6_service_path ``tracing_ab`` block (ISSUE 12): pin the A/B
    schema — the armed-unsampled (<1%) and 1%-sampled (<3%) budget
    verdicts — by running the helper directly on a small instance (the
    full svc section is a device-backend child; the block's contract
    is what the driver greps)."""
    sys.path.insert(0, REPO)
    import bench
    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.oracle import OracleEngine
    from gubernator_tpu.types import RateLimitRequest

    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      engine=OracleEngine())
    try:
        reqs = [RateLimitRequest(name="ab", unique_key=f"k{i}", hits=1,
                                 limit=1000, duration=60_000)
                for i in range(4)]
        row = bench._tracing_ab(
            inst, lambda r: inst.get_rate_limits(
                reqs, now_ms=1_791_000_000_000 + r),
            pairs=2, reps=4)
        assert "error" not in row, row
        for k in ("armed_overhead_pct", "overhead_ok",
                  "sampled_overhead_pct", "sampled_ok",
                  "off_calls_per_s", "pairs", "reps"):
            assert k in row, (k, row)
        assert isinstance(row["overhead_ok"], bool)
        assert isinstance(row["sampled_ok"], bool)
        assert row["off_calls_per_s"] > 0
        assert row["pairs"] == 2 and row["reps"] == 4
        # the A/B restores the recorder wiring it toggled
        assert inst.dispatcher.span_recorder is inst.span_recorder
        assert inst.span_recorder.sample == 0.0
    finally:
        inst.close()


def test_memledger_ab_block_schema():
    """The 6_service_path ``memledger_ab`` block (ISSUE 13): pin the
    A/B schema and its <1% steady-state budget verdict by running the
    helper directly on a small instance, and that the A/B leaves the
    ledger resumed (the toggle it flips must restore)."""
    sys.path.insert(0, REPO)
    import bench
    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.oracle import OracleEngine
    from gubernator_tpu.types import RateLimitRequest

    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      engine=OracleEngine())
    try:
        assert inst.memledger is not None
        reqs = [RateLimitRequest(name="ab", unique_key=f"k{i}", hits=1,
                                 limit=1000, duration=60_000)
                for i in range(4)]
        row = bench._memledger_ab(
            inst, lambda r: inst.get_rate_limits(
                reqs, now_ms=1_791_000_000_000 + r),
            pairs=2, reps=4)
        assert "error" not in row, row
        for k in ("overhead_pct", "overhead_ok", "on_calls_per_s",
                  "off_calls_per_s", "pairs", "reps"):
            assert k in row, (k, row)
        assert isinstance(row["overhead_ok"], bool)
        assert row["on_calls_per_s"] > 0
        assert row["off_calls_per_s"] > 0
        assert row["pairs"] == 2 and row["reps"] == 4
        # the A/B restores the ledger state it toggled
        assert inst.memledger.enabled is True
    finally:
        inst.close()


def test_scenario_ab_block_schema():
    """The 15_scenarios ``runner_ab`` block run directly on a small
    instance: schema + the JudgeTap's O(1) observe discipline (all
    per-row attribution deferred to finalize), same A/B pattern as
    ``memledger_ab``."""
    sys.path.insert(0, REPO)
    import bench
    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.oracle import OracleEngine
    from gubernator_tpu.types import RateLimitRequest

    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0),
                      engine=OracleEngine())
    try:
        reqs = [RateLimitRequest(name="ab", unique_key=f"k{i}", hits=1,
                                 limit=1000, duration=60_000)
                for i in range(8)]
        row = bench._scenario_ab(inst, reqs, pairs=2, reps=4)
        assert "error" not in row, row
        for k in ("overhead_pct", "overhead_ok", "on_calls_per_s",
                  "off_calls_per_s", "pairs", "reps", "rows"):
            assert k in row, (k, row)
        assert isinstance(row["overhead_ok"], bool)
        assert row["on_calls_per_s"] > 0
        assert row["off_calls_per_s"] > 0
        assert row["pairs"] == 2 and row["reps"] == 4
        assert row["rows"] == 8
    finally:
        inst.close()


def test_audit_ab_block_schema():
    """The 16_fleet ``audit_ab`` block run directly on a small
    instance: schema + that the A/B restores the tap it toggled."""
    sys.path.insert(0, REPO)
    import bench
    from gubernator_tpu.config import Config
    from gubernator_tpu.instance import V1Instance
    from gubernator_tpu.types import Behavior, RateLimitRequest

    # a real engine: the A/B drives the columnar GLOBAL wire lane,
    # which the pure-python OracleEngine reference lane doesn't serve
    inst = V1Instance(Config(cache_size=1 << 10, sweep_interval_ms=0))
    try:
        reqs = [[RateLimitRequest(name="ab", unique_key=f"k{i}",
                                  hits=1, limit=1000,
                                  duration=86_400_000,
                                  behavior=Behavior.GLOBAL)
                 for i in range(4)]]
        datas = bench._serialize_reqs(reqs)
        row = bench._audit_ab(inst, datas, pairs=2, reps=4)
        assert "error" not in row, row
        for k in ("overhead_pct", "overhead_ok", "on_calls_per_s",
                  "off_calls_per_s", "pairs", "reps"):
            assert k in row, (k, row)
        assert isinstance(row["overhead_ok"], bool)
        assert row["on_calls_per_s"] > 0
        assert row["pairs"] == 2 and row["reps"] == 4
        # the A/B restores the tap it toggled
        assert inst.global_manager.audit is not None
    finally:
        inst.close()


def test_section_registry_covers_baseline_rows():
    """Every BASELINE row key the orchestrator may need to error-fill
    is declared by exactly one section."""
    sys.path.insert(0, REPO)
    import bench

    declared = [k for _, keys in bench._SECTIONS.values() for k in keys]
    assert len(declared) == len(set(declared)), "duplicate row keys"
    for row in ["1_single_key_smoke", "2_leaky_1k_keys",
                "4_global_sharded", "5_gregorian_churn",
                "6_service_path", "8_peer_path",
                "9_clustered_service", "10_reuseport_group",
                "11_pallas_serving", "12_mesh_global",
                "13_tiered_store", "15_scenarios", "16_fleet"]:
        assert row in declared, row
    for name in bench._SECTION_ORDER:
        assert name in bench._SECTIONS


def test_flagship_defaults_are_the_round5_shape():
    """The driver runs `python bench.py` with NO env: the defaults ARE
    the flagship claim: CAP 2^26 for 10M keys, and the probe window the
    serving default (core/step.py › PROBES: 16 since PR 31 — 8 loses a
    key in one 10M key set in four; round 5's worry that a 16-probe
    window takes the serialized scatter lowering was priced on the
    chip, PERF.md §6 PR 31).  Import in a child: bench's module-level
    env defaults must not leak here."""
    code = (
        "import os, json\n"
        "import bench\n"
        "print(json.dumps({'cap': bench.CAP, 'n_keys': bench.N_KEYS,\n"
        "    'probes_env': os.environ.get('GUBER_PROBES', '')}))\n"
    )
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("GUBER_")}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       timeout=120, stdout=subprocess.PIPE,
                       stderr=subprocess.PIPE)
    assert r.returncode == 0, r.stderr.decode()[-500:]
    got = json.loads(r.stdout.decode().strip().splitlines()[-1])
    assert got["cap"] == 1 << 26, got
    assert got["n_keys"] == 10_000_000, got
    # bench must NOT export a probe override anymore: the serving
    # default (core/step.py PROBES) is the flagship window
    assert got["probes_env"] == "", got


def test_lint_clean_and_compile_ledger_provenance_schema():
    """The ``extra.lint_clean`` provenance block (ISSUE 14): pin its
    schema — clean flag, pass/violation counts, and the compile-ledger
    verdict whose shape row 6_service_path's ``compile_ledger`` block
    shares (both come from CompileLedger.verdict())."""
    sys.path.insert(0, REPO)
    import bench
    from tools.guberlint import PASS_NAMES

    block = bench._lint_clean()
    assert block is not None, "lint probe failed entirely"
    assert set(block) == {"clean", "passes", "violations",
                          "compile_ledger"}
    assert block["clean"] is True and block["violations"] == 0
    assert block["passes"] == len(PASS_NAMES) == 9
    cl = block["compile_ledger"]
    assert cl is not None, "compile ledger probe failed"
    assert set(cl) == {"enabled", "installed", "marked_steady",
                       "total_compiles", "steady_recompiles", "steady"}
    assert isinstance(cl["steady_recompiles"], dict)
    assert isinstance(cl["steady"], bool)
