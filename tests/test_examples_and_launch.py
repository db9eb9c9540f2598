"""End-to-end tests of launch drivers and examples (CPU, smoke configs)."""

import os
import subprocess
import sys

import pytest


def run_script(args, timeout=560):
    # Inherit the parent env (notably JAX_PLATFORMS: without it, jax probes
    # for TPU hardware and stalls ~8 min per subprocess on TPU-less images).
    r = subprocess.run(
        [sys.executable] + args,
        capture_output=True,
        text=True,
        timeout=timeout,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=".",
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return r.stdout


@pytest.mark.slow
def test_train_driver_smoke():
    out = run_script(
        [
            "-m", "repro.launch.train", "--arch", "qwen1.5-0.5b", "--smoke",
            "--steps", "12", "--batch", "4", "--seq", "64", "--log-every", "4",
        ]
    )
    assert "done: 12 steps" in out


@pytest.mark.slow
def test_train_driver_with_checkpointing(tmp_path):
    out = run_script(
        [
            "-m", "repro.launch.train", "--arch", "gemma2-2b", "--smoke",
            "--steps", "8", "--batch", "2", "--seq", "32",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "4",
        ]
    )
    assert "finished at step" in out


@pytest.mark.slow
def test_serve_driver_smoke():
    out = run_script(
        [
            "-m", "repro.launch.serve", "--arch", "qwen1.5-0.5b", "--smoke",
            "--requests", "3", "--gen-len", "4", "--batch", "2",
            "--max-len", "64",
        ]
    )
    assert "served 3 requests" in out


def test_example_serve_paged_decode():
    out = run_script(["examples/serve_paged_decode.py"])
    assert "paged vs contiguous" in out and "OK" in out


@pytest.mark.slow
def test_example_serve_paged_model():
    out = run_script(["examples/serve_paged_model.py"])
    assert "== dense" in out and "one per step, never per layer" in out
    assert "OK" in out


@pytest.mark.slow
def test_serve_driver_paged_backend():
    out = run_script(
        [
            "-m", "repro.launch.serve", "--cache", "paged", "--smoke",
            "--interpret",
            "--requests", "2", "--gen-len", "4", "--batch", "2",
        ]
    )
    assert "paged cache backend" in out and "served 2 requests" in out
    assert "prefill compiles: 1" in out


@pytest.mark.slow
def test_serve_driver_shared_prefix_demo():
    out = run_script(
        [
            "-m", "repro.launch.serve", "--cache", "paged", "--smoke",
            "--interpret",
            "--shared-prefix", "--gen-len", "3",
        ]
    )
    assert "pages aliased" in out and "zero rows copied" in out


def test_example_serve_shared_prefix():
    out = run_script(["examples/serve_shared_prefix.py"])
    assert "x dedup" in out and "shared-prefix vs contiguous" in out
    assert "OK" in out


@pytest.mark.slow
def test_example_long_context_decode():
    out = run_script(["examples/long_context_decode.py"])
    assert "rel err" in out


@pytest.mark.slow
def test_example_quickstart():
    out = run_script(["examples/quickstart.py"], timeout=580)
    assert "AMLA err" in out
