"""TPU build gate for every program the TPU default runs, no TPU
required.

tools/lower_check.py cross-lowers the Mosaic decision kernel, the
Mosaic sweep kernel, the fused serving program and the three XLA step
modes for the TPU target on the CPU backend, and — where the installed
libtpu offers a compile-only client — compiles the kernel programs
with the real Mosaic compiler.  Kernel bugs that lowering alone lets
through (the (8, 128) tiling of a table's HBM layout, DMA slice
alignment) are caught at that depth; this test keeps them caught.

Runs in a subprocess: the check needs its own interpreter (platform
config + x64 are set at import time, and conftest's 8-device CPU setup
must not leak in).
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KERNEL_PROGRAMS = ("pallas_step", "pallas_sweep", "pallas_fused_serving")
XLA_PROGRAMS = ("xla_step", "xla_step_donated", "xla_step_donated_ksplit21")


def _clean_env() -> dict:
    # minimal env: conftest mutates XLA_FLAGS/JAX_* at import time and
    # forwarding them would make this gate test a different config than
    # a standalone run of the tool
    return {k: v for k, v in os.environ.items()
            if not (k.startswith(("JAX_", "XLA_")) or k.startswith("GUBER_"))}


def test_every_tpu_default_program_builds_for_tpu():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lower_check.py")],
        capture_output=True, text=True, timeout=600, env=_clean_env())
    assert r.returncode == 0, f"build check failed:\n{r.stdout}\n{r.stderr}"
    for name in XLA_PROGRAMS:
        assert f"{name}: lowers for TPU" in r.stdout, r.stdout
    # an installation that ships the TPU compiler must have used it
    depth = ("compiles for TPU v5e"
             if importlib.util.find_spec("libtpu") is not None
             else "lowers for TPU")
    for name in KERNEL_PROGRAMS:
        assert f"{name}: {depth}" in r.stdout, r.stdout


# ---- the table of 32-bit words keeps the step's cost at the wave ------
#
# XLA:TPU carries an int64 as two 32-bit words: a program handed a
# table-sized 64-bit column splits all of it at entry and recombines it
# at exit (PERF.md §6, PR 31: ~60 of a 69-ms step at 2^26 rows).  The
# table holds words (core/table.py), so the rewriter's split / combine
# calls touch wave-sized values only and the module's temporaries do not
# follow the table.  Compiled for a DESCRIBED v5e by
# tools/xla_engine_cost.py (the same subprocess rule as above), at two
# capacities in one process.

CAPS = (1 << 18, 1 << 20)
WAVE = 1024
PROGRAMS = ("xla_step_packed", "sweep")


@pytest.fixture(scope="module")
def compiled():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler in this installation")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "xla_engine_cost.py"),
         "step", ",".join(str(c.bit_length() - 1) for c in CAPS), str(WAVE)],
        capture_output=True, text=True, timeout=600, env=_clean_env())
    assert r.returncode == 0, f"compile failed:\n{r.stdout}\n{r.stderr[-3000:]}"
    reports = [json.loads(ln) for ln in r.stdout.splitlines()
               if ln.startswith("{")]
    return {(d["program"], d["rows"]): d for d in reports}


@pytest.mark.parametrize("cap", CAPS)
@pytest.mark.parametrize("program", PROGRAMS)
def test_no_64_bit_split_or_combine_is_table_shaped(compiled, program, cap):
    d = compiled[program, cap]
    assert d["x64_table_shaped"] == []
    # what is left is the wave's: the [8, B] upload, `now`, the [5, B]
    # download and the two counters — the same handful at any capacity
    assert d["x64_split"] + d["x64_combine"] <= 8
    assert (d["x64_split"], d["x64_combine"]) == (
        compiled[program, CAPS[0]]["x64_split"],
        compiled[program, CAPS[0]]["x64_combine"])


@pytest.mark.parametrize("program", PROGRAMS)
def test_temporaries_do_not_grow_with_the_table(compiled, program):
    small, large = (compiled[program, c]["temp_bytes"] for c in CAPS)
    # four times the rows; a temporary that followed the table would
    # add 3 x 2^18 x 4 B = 3 MB a word column.  (Below ~2^24 rows the
    # compiler may stage whole columns in VMEM; that is no HBM
    # temporary and is not counted here.)
    assert large <= small + (1 << 20), (small, large)
    assert large < 50e6
