"""CPU rehearsal of every cell of BENCHMARK.json end to end at tiny
sizes (the configurations' and mixes' own ``rehearsal`` sections): the
same flow as on the chip, no number of which is a device number."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = [w["name"] for w in json.load(
    open(os.path.join(REPO, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell, trace):
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2200000123", "--seconds", "4",
         "--trace", str(trace), "--cpu-rehearsal"],
        capture_output=True, text=True, cwd=REPO, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, p.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    mine = lambda m: cell in m.get("workloads", [cell])  # noqa: E731
    if trace == 0:
        want = {m["name"] for m in manifest["end_to_end"] if mine(m)}
        assert set(line["metrics"]) == want
        assert all(v["value"] > 0 for v in line["metrics"].values())
    else:
        # device-trace readers find no device plane on the CPU and
        # return nothing; the host-side ones must all be there
        host = {m["name"] for m in manifest["per_layer"]
                if mine(m) and m["source"] != "device_trace"}
        assert host <= set(line["metrics"]), host - set(line["metrics"])


def test_no_accelerator_is_an_error():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=REPO, env=env,
        timeout=600)
    assert p.returncode != 0 and "{" not in p.stdout
