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


KEY_PROBE = """
import os, sys
import jax, jax.numpy as jnp
from dynamo_tpu.runtime.compile_cache import enable_compile_cache
enable_compile_cache()
scope, op_pad, caller_pad = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
ns = {"jax": jax, "jnp": jnp, "scope": scope}
exec(compile("\\n" * op_pad + "def step(x):\\n"
             "    with jax.named_scope(scope):\\n"
             "        return jnp.sin(x) * 2\\n", "/elsewhere/model.py",
             "exec"), ns)
exec(compile("\\n" * caller_pad + "def run(f, x):\\n    return f(x)\\n",
             "/elsewhere/engine.py", "exec"), ns)
step = jax.jit(ns["step"])
ns["run"](step, jnp.ones((4,))).block_until_ready()
# the compiled program still names its ops by scope
print(step.lower(jnp.ones((4,))).compile().as_text())
"""


def test_the_key_holds_scope_names_and_no_source_line(tmp_path):
    """A trace must name an op after the program that is running, so
    another scope is another entry; the source lines that would come
    with the names are left off, so an edit that only moves lines, of
    the model or of what calls it, recompiles nothing."""
    placed = tmp_path / "placed"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(placed))

    def entries_after(scope, op_pad, caller_pad):
        proc = subprocess.run(
            [sys.executable, "-c", KEY_PROBE, scope, str(op_pad),
             str(caller_pad)], env=env, cwd=str(tmp_path),
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-800:]
        assert "moe/sin" in proc.stdout or "experts/sin" in proc.stdout
        return {f for f in os.listdir(placed) if f.startswith("jit_step")}

    first = entries_after("moe", 0, 0)
    assert len(first) == 1
    assert entries_after("moe", 0, 5) == first, "the caller's lines moved"
    assert entries_after("moe", 3, 5) == first, "the op's line moved"
    assert len(entries_after("experts", 3, 5)) == 2, "same HLO, other name"


def test_registered_as_external_and_gitignored():
    from dynamo_tpu.runtime.config import ENV_REGISTRY

    assert ENV_REGISTRY["JAX_COMPILATION_CACHE_DIR"].component == "external"
    assert "DYN_BENCH_PROBE_TIMEOUT" not in ENV_REGISTRY
    assert "DYN_BENCH_WALL_BUDGET" not in ENV_REGISTRY
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
