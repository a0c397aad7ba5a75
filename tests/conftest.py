"""Test configuration: force an 8-device virtual CPU mesh so sharding
semantics are testable without TPU hardware (SURVEY.md §4 TPU test plan)."""

import os

# tests run on the CPU: JAX_PLATFORMS is set here, before anything has
# imported jax (the variable is read at import; nothing imports jax ahead
# of this file), so a plain `pytest` never reaches for an accelerator.
# DYN_TEST_TPU=1 leaves the platform to the environment instead.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
if not os.environ.get("DYN_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"

import asyncio  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def run_async():
    """Run an async fn to completion on a fresh event loop."""

    def _run(coro):
        return asyncio.run(coro)

    return _run


@pytest.fixture
def device_subprocess(tmp_path):
    """Write a worker script and run it in a subprocess whose XLA_FLAGS
    force exactly N virtual CPU devices BEFORE jax imports (the flag is
    read once at backend init, so in-process monkeypatching cannot do
    this). Shared by test_tp_serving and test_sharded_serving — see
    tests/device_harness.py."""
    from device_harness import run_device_subprocess

    def _run(source: str, *args, devices: int = 8, timeout: float = 600,
             env: dict = None):
        script = tmp_path / "device_worker.py"
        script.write_text(source)
        return run_device_subprocess(script, args, devices=devices,
                                     timeout=timeout, env_extra=env)

    return _run
