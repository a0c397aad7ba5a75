"""One collection rule for the benchmark's tests.

``test_bm_traffic.py::test_lengths_stay_inside_their_limits_and_the_context``
runs over every file under ``benchmark/traffic/`` and holds each request
to ``prompt_len + output_len < 4096``: the context of the cells the
benchmark had when it was written (PR 23). ``doc-qa`` (PR 31) is the first
mix of long documents: 8,257 to 8,449 prompt tokens by design, in a cell
whose page bucket holds 9,216. A ``model_config`` PR may add files under
``tests/benchmark/`` and may not edit one that is there, so that one case
is skipped here and ``test_bm_kanana.py`` holds ``doc-qa`` to its own
limits and its own cell's context instead (the same three assertions,
with the cell's bucket in place of 4,096). For a ``benchmark`` issue: read
the context from the cells that run a mix, in the test itself, and drop
this file.
"""

import pytest

LONG_CONTEXT = {
    "test_lengths_stay_inside_their_limits_and_the_context[doc-qa]":
        "doc-qa is held to its own cell's context in test_bm_kanana.py "
        "(the 4,096 here is the context of the cells of PR 23)",
}


def pytest_collection_modifyitems(config, items):
    """Skips exactly the cases named above, and refuses a collection of
    ``test_bm_traffic.py``'s length test in which one of them is no
    longer found: an id that stopped matching (the test or the mix was
    renamed) would otherwise leave a stale exemption here, unseen."""
    length_tests = [item for item in items
                    if item.fspath.basename == "test_bm_traffic.py"
                    and item.name.split("[")[0] == next(iter(
                        LONG_CONTEXT)).split("[")[0]]
    found = set()
    for item in length_tests:
        reason = LONG_CONTEXT.get(item.name)
        if reason:
            item.add_marker(pytest.mark.skip(reason=reason))
            found.add(item.name)
    # a run narrowed by -k / a node id may collect only some cases; the
    # whole parametrised test collected without a listed id is the fault
    if len(length_tests) > 1 and found != set(LONG_CONTEXT):
        raise pytest.UsageError(
            f"tests/benchmark/conftest.py skips {sorted(LONG_CONTEXT)} "
            f"but collection found only {sorted(found)} among "
            f"{[i.name for i in length_tests]}: drop or rename the entry")
