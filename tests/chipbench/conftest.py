"""The benchmark file the tests under ``tests/chipbench/`` read, twice: the
shipped ``BENCHMARK.json``, and a copy to which a configuration, a cell and
two per-layer metrics were appended the way a PR that changes the program
may append them (``chipbench_tiny.append``), so with entries after the end
of every list that such a PR may lengthen. A test that holds something of
the shipped file's lists takes the file through ``case`` / ``bench`` and
runs on both: one that pins the end of a list fails on the copy, in the PR
that writes it. To try an addition of your own, give ``case`` a third
parameter that writes it."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for path in (REPO, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import chipbench_tiny  # noqa: E402
from chipbench import run as harness  # noqa: E402

BENCH = os.path.join(REPO, "chipbench")
SHIPPED = os.path.join(REPO, "BENCHMARK.json")


@pytest.fixture(scope="module", params=["shipped", "appended"])
def case(request, tmp_path_factory):
    """``path`` of a benchmark file and the ``roots`` its names are found
    under (``harness.find``): the shipped ones, or a copy with new entries
    at the end of its lists and their files in a directory in front."""
    if request.param == "shipped":
        return types.SimpleNamespace(path=SHIPPED, roots=[BENCH],
                                     appended=False)
    root = tmp_path_factory.mktemp("appended")
    path, roots = chipbench_tiny.append(str(root), SHIPPED)
    return types.SimpleNamespace(path=path, roots=roots + [BENCH],
                                 appended=True)


@pytest.fixture(scope="module")
def bench(case):
    return harness.load_json(case.path)
