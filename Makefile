# gubernator-tpu build/test targets (reference: Makefile).

PY ?= python

.PHONY: test proto bench bench-pallas bench-tiered bench-diff chaos \
        scenarios fleet-audit chip-smoke daemon cluster lint \
        native tsan asan racer check clean

test:
	$(PY) -m pytest tests/ -q

# whole-program correctness suite (tools/guberlint/, see
# CONCURRENCY.md): guarded-by, lock order, GUBER_* env registry,
# faultpoint catalog, thread inventory, clock-domain taint,
# traced-code purity, retrace stability, operator-doc consistency.
# Zero violations at HEAD is a tier-1 invariant and the full suite
# must finish inside the pinned 30 s wall-clock budget — both
# enforced by tests/test_lint_clean.py.
lint:
	$(PY) -m tools.guberlint

# ThreadSanitizer build of ops/_native.cpp + the multithreaded native
# soak under it (tools/native_soak.py; suppressions: tools/tsan.supp).
# The production in-place .so is untouched — the instrumented build
# lands in build/tsan/.
tsan:
	GUBER_NATIVE_SAN=tsan $(PY) gubernator_tpu/ops/setup_native.py \
	    build_ext --build-lib build/tsan
	$(PY) tools/native_soak.py --san tsan

# AddressSanitizer twin of `make tsan` (build/asan/).
asan:
	GUBER_NATIVE_SAN=asan $(PY) gubernator_tpu/ops/setup_native.py \
	    build_ext --build-lib build/asan
	$(PY) tools/native_soak.py --san asan

# seeded interleaving harness: adversarial preemptions at the
# dispatcher merge/carry/splice faultpoints, conservation as oracle
racer:
	JAX_PLATFORMS=cpu $(PY) tools/racer.py --seed 1 --runs 2

# CI-style gate: static analysis + sanitizer soaks + the concurrency
# test subset + the compile-ledger gate (steady-state zero recompiles
# on the service path); the full tier-1 battery stays `make test`
check: lint tsan asan scenarios fleet-audit
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_guberlint.py \
	    tests/test_lint_clean.py tests/test_compileledger.py \
	    tests/test_created_at.py \
	    tests/test_cold_conservation.py tests/test_native.py \
	    tests/test_interval.py tests/test_dispatcher.py \
	    tests/test_scenarios.py -q

# the scenario lab's seeded fast subset (ISSUE 16): every spec in
# scenarios/ with its fast-mode overrides, every oracle armed
scenarios:
	JAX_PLATFORMS=cpu $(PY) tools/scenario_lab.py --fast

# faultpoint × {error,delay} matrix against an in-proc cluster; exits
# nonzero if any injected fault hangs the daemon or breaks recovery
chaos:
	$(PY) tools/chaos_matrix.py

# 3-daemon fleet conservation smoke (ISSUE 19, fleet.py): drive GLOBAL
# traffic, then fold every daemon's OWN GET /debug/audit vector and
# prove fleet drift == 0 at steady state with a consistent ring
fleet-audit:
	JAX_PLATFORMS=cpu $(PY) tools/fleet_audit_smoke.py

proto:
	cd gubernator_tpu/proto && protoc -I. --python_out=. \
	    gubernator.proto peers.proto

bench:
	$(PY) bench.py

# the fused-serving A/B row (11_pallas_serving) standalone: fused
# engine vs classic XLA on identical seeded wire traffic, with the
# PhaseLedger phase_deleted evidence (ISSUE 8)
bench-pallas:
	GUBER_BENCH_SECTION=pallas $(PY) bench.py

# the tiered-store capacity row (13_tiered_store) standalone: 1M-key
# seeded skewed traffic vs a 4K-row device cap + host cold tier,
# A/B'd byte-for-byte against an uncapped oracle (ISSUE 10)
bench-tiered:
	GUBER_BENCH_SECTION=tiered $(PY) bench.py

# perf-regression gate (ISSUE 13): diff the newest BENCH_r*.json
# against the previous round with per-metric tolerance; rows the run
# flagged environment-dominated (context/skipped_*/error) are skipped,
# truncated artifacts are declared incomparable (exit 0), regressions
# beyond tolerance exit 1
bench-diff:
	$(PY) tools/bench_compare.py

# the quickest proof that the service path starts on the chip: one
# process, both engines compiled, answers checked against the oracle
# (run it on a machine with a TPU; add --cpu-rehearsal to rehearse the
# same phases at tiny sizes on the CPU backend)
chip-smoke:
	$(PY) chip_smoke.py

daemon:
	$(PY) -m gubernator_tpu.cmd.daemon --config example.conf

cluster:
	$(PY) -m gubernator_tpu.cmd.cluster --count 4

native:
	$(PY) gubernator_tpu/ops/setup_native.py build_ext --inplace

clean:
	rm -rf build dist *.egg-info gubernator_tpu/ops/*.so
	find . -name __pycache__ -type d -exec rm -rf {} +
