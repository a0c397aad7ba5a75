"""Where the benchmark lies, found from this file's own path."""

import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def copy_benchmark(to) -> str:
    """BENCHMARK.json and benchmark/ copied under ``to`` (a test then
    edits the copy); returns it as the ``root`` the harness takes."""
    shutil.copytree(BENCH, os.path.join(to, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), to)
    return str(to)
