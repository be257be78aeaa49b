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
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KERNEL_PROGRAMS = ("pallas_step", "pallas_sweep", "pallas_fused_serving")
XLA_PROGRAMS = ("xla_step", "xla_step_donated", "xla_step_donated_ksplit21")


def test_every_tpu_default_program_builds_for_tpu():
    # minimal env: conftest mutates XLA_FLAGS/JAX_* at import time and
    # forwarding them would make this gate test a different config than
    # a standalone `python tools/lower_check.py`
    env = {k: v for k, v in os.environ.items()
           if not (k.startswith(("JAX_", "XLA_")) or k.startswith("GUBER_"))}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lower_check.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"build check failed:\n{r.stdout}\n{r.stderr}"
    for name in XLA_PROGRAMS:
        assert f"{name}: lowers for TPU" in r.stdout, r.stdout
    # an installation that ships the TPU compiler must have used it
    depth = ("compiles for TPU v5e"
             if importlib.util.find_spec("libtpu") is not None
             else "lowers for TPU")
    for name in KERNEL_PROGRAMS:
        assert f"{name}: {depth}" in r.stdout, r.stdout
