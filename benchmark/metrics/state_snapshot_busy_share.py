"""What snapshots of recurrent state by the page cost the device: the
time of the ops under the program's scope ``state.snapshot`` (the
restore of a row's state from the last hit page in its first chunk, the
choice of a row's state at a page's last token, and the scatter of those
rows into the pool by page id, in prefill and in the decode window) as a
share of the time an operation ran on the device, in the traced slice.
Matched by benchmark/harness/scope_ops.py. A program without the scope
(dynamo_tpu/models/lfm2.py has it) reports nothing."""

from benchmark.harness import scope_ops


def read(raw):
    return scope_ops.path_share(raw, "state.snapshot", __file__)
