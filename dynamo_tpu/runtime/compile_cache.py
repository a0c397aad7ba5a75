"""Persistent XLA compile cache, placeable from outside.

A cold warm-up compiles the whole bucket grid; a machine that keeps
``JAX_COMPILATION_CACHE_DIR`` between runs gets it back for the price of
reading files. The rule is one line: if that variable is set, JAX has
already read it and this code sets no directory; otherwise the cache
lives at ``<checkout>/.jax_cache`` — a fixed path derived from where
this package sits (the path is part of the cache's key, so a directory
named after a pid, a time or a temp file would never hit). The
checkout's own path is kept OUT of the key, so a cache placed from
outside is found again by another checkout of the same code.
"""

from __future__ import annotations

import os
import re

from .config import env_str
from .profiling import install_jit_listeners, setup_span

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@setup_span("jax_import")
def enable_compile_cache() -> str:
    """Switch the persistent compile cache on; returns the directory in
    use. Call before the first jit of any process that serves or
    benches (run.py, the SDK's TPU workers, bench.py, chip_smoke.py):
    it is the program's first ``import jax`` there (the set-up ledger's
    ``jax_import`` span), and it installs the ledger's jit listeners."""
    import jax

    install_jit_listeners()
    path = env_str("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # source locations ride into the cache key inside the Pallas kernel's
    # serialized module (the tpu_custom_call's opaque config): without
    # this every kernel-bearing program misses from a checkout at another
    # path (measured: 532 s of warm-up with the whole cache present)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(_CHECKOUT + os.sep))
    # the names a profiler trace shows (jax.named_scope paths, kernel
    # names) are metadata, which the key leaves out by default: a program
    # whose scopes changed is then handed the executable, and the names,
    # of the program compiled before it, and a trace names its ops after
    # code that is no longer there (seen in PR 24: parent and change
    # share prefill_step's key). With the names in the key, the source
    # lines that JAX attaches to every op (the op's own and ten calling
    # frames) would be in it too, and a comment added to the engine
    # would recompile the grid. So no frames are attached: a location is
    # the op's name-scope path alone, a pure line shift anywhere misses
    # nothing (JAX's defaults miss most of the grid on one, through the
    # frames in the Pallas kernels' serialized modules; PERF.md, PR 24,
    # has both measured), and what a profile loses is an op's `source`
    # file:line, which its path locates as well.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    # the warm grid is mostly programs that compile in under a second
    # each; JAX's default would skip caching exactly those
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
