"""Device time of the ops under the program's scope ``moe`` (router and
experts, in the decode window and in prefill) as a share of the time an
operation ran on the device, in the traced slice. The scope of an op is
read by benchmark/harness/host_trace.py; a program without the scopes
reports nothing."""

from benchmark.harness import host_trace


def read(raw):
    return host_trace.scope_share(raw, "moe", __file__)
