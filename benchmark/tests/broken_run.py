"""Helper of test_correct_fails.py: one CPU-rehearsal run of a cell with
the timed path broken underneath, where the answers are produced (the
native response builders the instance serialises every wave through).

    python benchmark/tests/broken_run.py <fault> <run.py arguments...>

faults
  float32_reset   reset_time goes through float32 (the lower-precision
                  control: epoch-ms needs 41 bits, float32 keeps 24)
  remaining_off   every 50th answer's remaining is one too high (a
                  decrement lost)
  none            nothing broken (the test's own control)
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def install(fault: str) -> None:
    from gubernator_tpu.ops import native

    def broken(cols):
        st, lim, rem, rst = (np.array(c) for c in cols[:4])
        if fault == "float32_reset":
            rst = rst.astype(np.float32).astype(np.int64)
        elif fault == "remaining_off":
            rem[::50] += 1
        return (st, lim, rem, rst) + tuple(cols[4:])

    from_columns, from_lists = (native.build_responses_from_columns,
                                native.build_rate_limit_resps)
    native.build_responses_from_columns = (
        lambda cols, lo, hi, errors=None:
        from_columns(broken(cols), lo, hi, errors))
    native.build_rate_limit_resps = (
        lambda st, lim, rem, rst, errors=None:
        from_lists(*broken((st, lim, rem, rst)), errors))


if __name__ == "__main__":
    fault = sys.argv.pop(1)
    from benchmark import run

    run.build_native()
    if fault != "none":
        install(fault)
    sys.exit(run.main())
