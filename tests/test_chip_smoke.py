"""chip_smoke.py's contract, as far as a machine without a chip can
hold it: the CPU rehearsal runs every phase end to end at tiny size and
labels its result `cpu`; without the rehearsal flag a CPU backend is a
failure that prints no result; and the script alone, outside the
repository, fails too."""
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE, timeout=600):
    # conftest's 8-device XLA_FLAGS and GUBER_* must not leak in: the
    # smoke chooses its own engine per pass
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("XLA_", "GUBER_"))}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, script] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_cpu_rehearsal_runs_every_phase_and_is_labelled_cpu():
    r = _run(["--cpu-rehearsal"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    out = r.stdout
    assert "CPU REHEARSAL" in out
    # both engines served, each phase reported
    assert "'engine': 'pallas-kernel'" in out
    assert "'engine': 'xla-classic'" in out
    for label in ("A", "B"):
        for phase in ("keys resident", "Zipf(1.1) stream",
                      "LEAKY_BUCKET slice", "sweep live count",
                      "HTTP limit=3 flow", "nothing swallowed"):
            assert f"pass {label}: " in out and phase in out, phase
    assert "GLOBAL on the 1-device mesh" in out
    assert "0 compiles after warm-up" in out


def test_without_the_rehearsal_flag_a_cpu_backend_is_a_failure():
    r = _run([])
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout
    assert '"ok"' not in r.stdout


def test_the_script_alone_outside_the_repo_fails(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run([], cwd=str(tmp_path), script=str(alone))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
