"""How busy the event-loop thread is: its CPU seconds as the kernel
counts them (``thread_cpu_seconds_total["loop"]``, read from
``/proc/self/task/<tid>/schedstat`` at ``stats()`` time) over the wall
time between the two reads. The thread writes every SSE stream and hands
the step thread each iteration; near 100% it is the bottleneck whatever
its ledger's slots say."""

from benchmark.harness import host_counters


def read(raw):
    return host_counters.share_of_wall(raw, host_counters.thread_seconds(
        raw, "thread_cpu_seconds_total", ("loop",)))
