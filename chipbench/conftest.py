"""One known failure of the benchmark's own tests, marked and not hidden.

``tests/test_weights.py::test_the_parent_recorded_every_case`` asserts that
the digest cases of the cells in ``BENCHMARK.json`` EQUAL the set PR 26's
parent recorded, so it fails as soon as any cell is added; every recorded
case still has its digest (``test_same_seed_same_bits_as_the_parent``). The
repair, ``set(PARENT) <= set(digests)``, is an edit to a file the benchmark
has: a ``benchmark`` PR's (PERF.md "For a benchmark issue"). The mark is
strict: once the test is repaired it fails as XPASS, and this file goes.
"""

import pytest

KNOWN = "test_weights.py::test_the_parent_recorded_every_case"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(KNOWN):
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="asserts equality with PR 26's set of "
                "cells; a new cell needs `<=` (a benchmark PR's edit)"))
