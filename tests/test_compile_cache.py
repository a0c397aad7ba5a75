"""runtime/compile_cache.py: a cache that can be placed from outside.
JAX_COMPILATION_CACHE_DIR set → the code sets no directory (JAX read the
variable itself); unset → <checkout>/.jax_cache, the same path from any
process, never built from a temp name, pid or time. Each case runs in a
subprocess: the helper re-points the process's cache for good."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import json, os, sys
import jax
calls = []
real = jax.config.update
def spy(name, val):
    calls.append(name)
    real(name, val)
jax.config.update = spy
from dynamo_tpu.runtime.compile_cache import enable_compile_cache
path = enable_compile_cache()
import jax.numpy as jnp
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
print(json.dumps({"path": path, "set_in_code": calls,
                  "strip": jax.config.jax_hlo_source_file_canonicalization_regex,
                  "jax_dir": jax.config.jax_compilation_cache_dir,
                  "pid": os.getpid()}))
"""


def probe(cwd, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_over)
    proc = subprocess.run([sys.executable, "-c", PROBE], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-800:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_variable_set_code_sets_no_directory(tmp_path):
    placed = tmp_path / "placed"
    out = probe(str(tmp_path), JAX_COMPILATION_CACHE_DIR=str(placed))
    assert out["path"] == str(placed) == out["jax_dir"]
    assert "jax_compilation_cache_dir" not in out["set_in_code"]
    # the checkout's path is stripped from source locations either way
    import re
    assert re.sub(out["strip"], "", REPO + "/dynamo_tpu/x.py") \
        == "dynamo_tpu/x.py"
    assert os.listdir(placed), "the program's compiles land where placed"


def test_unset_uses_the_checkout_path_from_any_process(tmp_path):
    a = probe(str(tmp_path))
    b = probe(REPO)
    want = os.path.join(REPO, ".jax_cache")
    assert a["path"] == b["path"] == want == a["jax_dir"]
    assert a["pid"] != b["pid"], "two processes, one path"
    assert "jax_compilation_cache_dir" in a["set_in_code"]
    for forbidden in (str(a["pid"]), "tmp", "temp"):
        assert forbidden not in os.path.relpath(a["path"], REPO).lower()
    assert os.listdir(want)


def test_registered_as_external_and_gitignored():
    from dynamo_tpu.runtime.config import ENV_REGISTRY

    assert ENV_REGISTRY["JAX_COMPILATION_CACHE_DIR"].component == "external"
    assert "DYN_BENCH_PROBE_TIMEOUT" not in ENV_REGISTRY
    assert "DYN_BENCH_WALL_BUDGET" not in ENV_REGISTRY
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
