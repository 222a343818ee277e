"""Shared pytest fixtures.  NOTE: no XLA_FLAGS here — the main test process
sees exactly 1 device; multi-device checks run in subprocesses
(repro.testing.*) with their own fake-device flags.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_multidev(module: str, *args: str, devices: int = 8, timeout: int = 1200):
    """Run ``python -m repro.testing.<module> args...`` with fake devices."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"    # fake host devices; never an accelerator
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", f"repro.testing.{module}", *map(str, args)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"{module} {args} failed (rc={proc.returncode})\n"
            f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
        )
    return proc.stdout


@pytest.fixture(scope="session")
def multidev():
    return run_multidev


@pytest.fixture(autouse=True)
def _isolated_plan_cache(tmp_path_factory, monkeypatch):
    """Keep the CLI's default-on plan cache out of ~/.cache during tests:
    every test gets a fresh, throwaway cache directory."""
    monkeypatch.setenv("REPRO_PLAN_CACHE",
                       str(tmp_path_factory.mktemp("plan-cache")))
