"""One pin of a file that PR 42 may not edit.

`test_build_reader.py` asserts that PR 41's metric is the LAST entry of
`per_layer`.  The benchmark grows by appending, so the first PR to add
a metric after it (42: six) makes that line false, and a PR that is no
`benchmark` PR may not change a file the benchmark has.  What else the
test checks of the entry is checked again, by membership, in
`test_settle_readers.py`.  For the next `benchmark` PR: make line 41 of
`test_build_reader.py` a membership check and delete this file (the
mark is strict: once the test passes again, the run fails here).
"""

import pytest

PINNED_LAST = ("test_build_reader.py::"
               "test_it_is_listed_where_its_end_to_end_metric_is_reported")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(PINNED_LAST):
            item.add_marker(pytest.mark.xfail(
                reason="asserts per_layer[-1]; PR 42 appended six entries",
                strict=True))
