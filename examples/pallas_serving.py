"""Example: serve from the Mosaic kernel (step_impl=pallas).

The pallas serving mode trades on-device auto-grow for a
lowering-independent step: the hand-scheduled kernel owns its table
scatters, so its cost does not depend on how the XLA backend of the
day lowers a 2^24-row scatter.  Use it when the XLA mode hits a
large-CAP lowering pathology (see `tools/cap_ab.py`), and size the
table up front — full 128-slot buckets turn NEW keys into table_full
errors, watched by the `gubernator_pallas_bucket_saturation` gauge.
Run: python examples/pallas_serving.py   (CPU runs the kernel in
interpret mode — correct but slow; the mode targets real TPUs.)
"""
import os
import time

from gubernator_tpu.config import Config
from gubernator_tpu.instance import V1Instance
from gubernator_tpu.types import RateLimitRequest


def main() -> None:
    # env beats Config in step_impl resolution — an exported
    # GUBER_STEP_IMPL would silently demo the wrong engine.  POP, not
    # set: this also runs via runpy inside the test process, where a
    # lingering export would flip the engine under every later test.
    os.environ.pop("GUBER_STEP_IMPL", None)
    # sizing rule (example.conf): cache_size >= 2.5x peak live keys
    inst = V1Instance(Config(cache_size=1 << 14, step_impl="pallas",
                             sweep_interval_ms=0))
    try:
        now_ms = int(time.time() * 1000)
        reqs = [RateLimitRequest(name="api", unique_key=f"user:{i}",
                                 hits=1, limit=100, duration=60_000)
                for i in range(512)]
        inst.get_rate_limits(reqs, now_ms=now_ms)  # compile + insert
        t0 = time.perf_counter()
        resps = inst.get_rate_limits(reqs, now_ms=now_ms + 10)
        dt = time.perf_counter() - t0
        under = sum(1 for r in resps if int(r.status) == 0)
        full, total = inst.engine.bucket_saturation()
        print(f"512 decisions in {dt * 1e3:.1f}ms over the kernel; "
              f"under_limit={under}, "
              f"bucket saturation {full}/{total} full")
    finally:
        inst.close()


if __name__ == "__main__":
    main()
