"""Device time of the ops under a ``jax.named_scope`` that
``host_trace.SCOPES`` does not list.

``host_trace.scope_share`` knows a fixed tuple of scopes, and an op under
any other name counts there as ``unscoped``. A reader of a scope a later
PR added (``ssm``, ``ssm.scan``: dynamo_tpu/models/jamba.py) takes the
run's ops from ``host_trace._run_trace`` (each carries its ``tf_op``
name-stack path) and matches the path's components itself, here, with
the guards ``scope_share`` has.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import counters, host_trace, trace


def path_seconds(raw: dict, scope: str, reader_file: str
                 ) -> Optional[float]:
    """Device seconds of the ops whose name-stack path has the component
    ``scope`` (``.../ssm/ssm.scan/while/body/...`` has ``ssm`` and
    ``ssm.scan``), averaged over the chips, in the traced slice of the
    run ``raw`` came from. Ops that only contain other ops are left out.
    None where the run was not traced, the trace found is another run's
    (``host_trace._run_trace``), the program is one without scopes (its
    ``stats()`` lacks the phases of the same PR), or no op carries any
    scope at all, neither ``scope`` nor one of ``host_trace.SCOPES``."""
    if counters.PHASES_KEY not in raw.get("stats1", {}):
        return None
    found = host_trace._run_trace(raw, reader_file)
    if found is None:
        return None
    loaded, got = found
    total, hits = 0.0, 0
    for ops in loaded["ops"].values():
        for name, _, d, tf_op in ops:
            if trace.CONTAINER_OP.match(trace._op(name)[0]):
                continue
            if scope in tf_op.rstrip(":").split("/"):
                total += d
                hits += 1
    if not hits and set(got["scopes"]) <= {"unscoped"}:
        return None
    return total / len(loaded["ops"])


def path_share(raw: dict, scope: str, reader_file: str) -> Optional[float]:
    """100 x ``path_seconds`` / the time an operation ran on the device."""
    seconds = path_seconds(raw, scope, reader_file)
    if seconds is None:
        return None
    return 100.0 * seconds / raw["trace"]["busy_s"]
