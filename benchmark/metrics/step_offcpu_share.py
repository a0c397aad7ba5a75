"""Of the step thread's wall time in the phases where it does its own
work, the share it was NOT on a CPU: (wall - CPU) / wall over every phase
but the readbacks (blocked on the device), ``idle`` and ``between_steps``
(waiting for the event loop: ``step_gap_ms_mean`` has that).
``step_phase_cpu_seconds_total`` is ``time.thread_time()`` read beside
``perf_counter`` at every phase switch. Off-CPU time in a work phase is
the GIL (the loop and detokeniser threads hold it) or the kernel's run
queue (``stats()["thread_runq_wait_seconds_total"]`` tells them apart
where the kernel keeps it; the benchmark's machines' does not)."""

from benchmark.harness import counters, host_counters


def read(raw):
    wall = counters.phase_deltas(raw)
    cpu = host_counters.dict_delta(raw, host_counters.CPU_KEY)
    if not wall or cpu is None:
        return None
    work = [k for k in wall if k not in host_counters.NOT_WORK]
    total = sum(wall[k] for k in work)
    if total <= 0:
        return None
    return 100.0 * (total - sum(cpu.get(k, 0.0) for k in work)) / total
