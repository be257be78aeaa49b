"""The benchmark's yardstick under the tier-1 command, which collects
``tests/`` only: every case of ``benchmark/tests/test_plugins.py`` (pure
numpy, no daemon) — the LEAKY_BUCKET window rules sound on the plain
reference in random serial orders and tight on each control and on one
altered answer a rule, the leaky reference on hand-worked cases, the key
draws, every plug-in file against its seam, and the golden digests of
the existing cells' request bytes.  The cell ``r1-leaky-b1000-sat`` is
judged by these rules; nothing else guards them in CI."""
import pytest

pytest.register_assert_rewrite("benchmark.tests.test_plugins")

from benchmark.tests.test_plugins import *  # noqa: E402,F401,F403
