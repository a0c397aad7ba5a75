"""Two-process multi-host SPMD bootstrap (VERDICT r4 task 6): proves
``initialize_multihost`` — the replacement for the reference's Ray
bootstrap (lib/llm/src/engines/vllm/ray.rs) — actually executes:
2 OS processes × 2 virtual CPU devices each join one jax.distributed
group, build the global 2x2 data×model mesh, and run a sharded forward
whose shards match a local oracle (tests/multihost_worker.py)."""

import os
import socket
import subprocess
import sys

WORKER = os.path.join(os.path.dirname(__file__), "multihost_worker.py")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_spmd_forward():
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=2"])
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, WORKER, coord, "2", str(pid)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\n{out}\n{err[-3000:]}"
        assert "MULTIHOST-OK" in out, out
        assert "procs=2" in out and "global_devices=4" in out, out
