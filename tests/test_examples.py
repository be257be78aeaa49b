"""Doc-example smoke tests (reference: examples_test.go — BASELINE
config 1's named source)."""
import runpy
import sys


def test_single_daemon_example(capsys):
    runpy.run_path("examples/single_daemon.py", run_name="__main__")
    out = capsys.readouterr().out
    assert "status=UNDER_LIMIT" in out
    assert "remaining=9" in out


def test_embedded_engine_example(capsys):
    runpy.run_path("examples/embedded_engine.py", run_name="__main__")
    out = capsys.readouterr().out
    assert "decisions in" in out


def test_global_mesh_example(capsys):
    runpy.run_path("examples/global_mesh.py", run_name="__main__")
    out = capsys.readouterr().out
    assert "mesh keys pinned: 1" in out
    assert "4032 of 4032 hits folded" in out


def test_pallas_serving_example(capsys, monkeypatch):
    monkeypatch.delenv("GUBER_STEP_IMPL", raising=False)
    runpy.run_path("examples/pallas_serving.py", run_name="__main__")
    out = capsys.readouterr().out
    assert "over the kernel" in out
    assert "under_limit=512" in out
    assert "bucket saturation 0/" in out
