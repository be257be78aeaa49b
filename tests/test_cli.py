"""CLI end-to-end: real subprocess daemon + healthcheck + load CLI
(reference: cmd/ binaries — SURVEY.md §2.1)."""
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

from gubernator_tpu.netutil import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def daemon_proc():
    grpc_port, http_port = free_port(), free_port()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        # JAX_COMPILATION_CACHE_DIR is inherited from os.environ
        # (conftest ran _jax_cache.setup()), so the daemon subprocess
        # shares the warm repo-local cache
        GUBER_CACHE_SIZE="4096",
    )
    p = subprocess.Popen(
        [sys.executable, "-m", "gubernator_tpu.cmd.daemon",
         "--grpc", f"127.0.0.1:{grpc_port}",
         "--http", f"127.0.0.1:{http_port}"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    # wait until healthy (first compile can take a while)
    url = f"http://127.0.0.1:{http_port}/v1/HealthCheck"
    deadline = time.time() + 120
    last = None
    while time.time() < deadline:
        if p.poll() is not None:
            out, err = p.communicate()
            raise RuntimeError(f"daemon died: {err.decode()[-2000:]}")
        try:
            with urllib.request.urlopen(url, timeout=2) as f:
                if json.loads(f.read())["status"] == "healthy":
                    break
        except Exception as e:  # noqa: BLE001
            last = e
            time.sleep(0.5)
    else:
        p.kill()
        raise RuntimeError(f"daemon never became healthy: {last}")
    yield {"grpc": f"127.0.0.1:{grpc_port}",
           "http": f"127.0.0.1:{http_port}", "proc": p}
    p.send_signal(signal.SIGTERM)
    try:
        p.wait(timeout=15)
    except subprocess.TimeoutExpired:
        p.kill()


def run_cmd(mod, *args, timeout=120):
    return subprocess.run(
        [sys.executable, "-m", mod, *args], cwd=REPO, env=dict(
            os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=timeout)


def test_healthcheck_cli(daemon_proc):
    r = run_cmd("gubernator_tpu.cmd.healthcheck",
                "--url", f"http://{daemon_proc['http']}/v1/HealthCheck")
    assert r.returncode == 0, r.stderr
    assert "healthy" in r.stdout


def test_healthcheck_cli_down():
    r = run_cmd("gubernator_tpu.cmd.healthcheck",
                "--url", "http://127.0.0.1:1/v1/HealthCheck", "--timeout", "1")
    assert r.returncode == 1


def test_load_cli_grpc(daemon_proc):
    r = run_cmd("gubernator_tpu.cmd.cli",
                "--address", daemon_proc["grpc"],
                "--rate-limits", "500", "--batch", "50",
                "--concurrency", "2", "--duration", "2", "--json")
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["decisions"] > 0
    assert out["p99_ms"] is not None


def test_load_cli_http(daemon_proc):
    r = run_cmd("gubernator_tpu.cmd.cli",
                "--address", daemon_proc["http"], "--http",
                "--rate-limits", "100", "--batch", "20",
                "--concurrency", "1", "--duration", "1", "--json")
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["decisions"] > 0
