"""Test config: an 8-device CPU platform (the reference's
cluster/cluster.go in-process multi-daemon analog, SURVEY.md §4).

Tests run on the CPU backend — JAX_PLATFORMS=cpu, the one way to
choose a backend — with 8 virtual devices, set before jax is imported.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
# Persistent compile cache: the step program is large; don't re-pay XLA
# compilation on every pytest invocation.
from gubernator_tpu import compilecache  # noqa: E402

compilecache.setup()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", True)

# Build the C++ host-ops extension if this checkout hasn't yet (fresh
# clones ship no build outputs).  A checkout that cannot build it must
# fail here: the wire-lane tests exercise it, and a quiet pb2 lane
# would turn them into false greens.
try:
    from gubernator_tpu.ops import _native  # noqa: F401
except ImportError:
    import subprocess

    subprocess.run([sys.executable, "gubernator_tpu/ops/setup_native.py",
                    "build_ext", "--inplace"], cwd=REPO, check=True,
                   capture_output=True)


@pytest.fixture(scope="session")
def cpu_mesh():
    """Shared 4-device mesh (one compiled step program per mesh shape)."""
    from gubernator_tpu.parallel import make_mesh

    return make_mesh(n=4)


@pytest.fixture
def serial_only():
    """``serial_only(engine)``: the same engine WITHOUT ``launch_packed``
    — what the dispatcher's serial branch serves.  (OracleEngine, the
    one capability-less engine in the tree, has no columnar entry at
    all; this keeps check_packed, so both worker branches can be held
    to the same answers.)  Entries are bound when it is built: gate the
    engine's methods first."""

    class SerialOnly:
        def __init__(self, eng):
            self.check_packed = eng.check_packed
            self.check_batch = eng.check_batch

    return SerialOnly


@pytest.fixture
def numpy_calls():
    """Context manager that counts what a block asks of numpy: every
    numpy function and ndarray method the profiler sees in the calling
    thread (array operators make no call event and ride with these).
    ``with numpy_calls() as c: ...; c.n``"""
    import numpy as np

    class Count:
        n = 0

        def __enter__(self):
            def prof(frame, event, arg):
                if event == "c_call" and (
                        (getattr(arg, "__module__", None)
                         or "").startswith("numpy")
                        or isinstance(getattr(arg, "__self__", None),
                                      np.ndarray)):
                    self.n += 1

            sys.setprofile(prof)
            return self

        def __exit__(self, *exc):
            sys.setprofile(None)

    return Count
