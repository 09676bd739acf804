"""The two trees a family's manifest test runs on (``manifest_cells.py``
has the rule; ``benchmark/README.md``, "A family's test"): the repo's, and
a later PR's, which reads JSON and copies small files: no model is built."""

import pytest

from benchmark import harness

import manifest_cells

TREES = ["as it stands", "with a later PR's cell"]


@pytest.fixture(scope="session")
def a_later_prs_tree(tmp_path_factory):
    """``tests/benchmark/files_only/`` laid over a copy of the benchmark's
    data, its manifest entries appended: ``(root, bytes of every file that
    was there before)``. One copy a session: no test writes into it."""
    return manifest_cells.lay_a_later_prs_tree(
        tmp_path_factory.mktemp("a_later_prs_tree"), manifest_cells.read_manifest())


@pytest.fixture(params=TREES)
def either_tree(request, a_later_prs_tree):
    """``(manifest, root)`` of the benchmark as it stands, then of the tree
    with a later PR's cells, configuration and per-layer metrics added after
    everything that is there. A family's manifest test takes this fixture
    (``test_benchmark_manifest.py`` holds every family's to it), so a test
    that pins a position, counts the cells or keeps a list of the other
    cells fails on the second case in the PR that writes it."""
    root = harness.HERE if request.param == TREES[0] else str(a_later_prs_tree[0])
    return manifest_cells.read_manifest(root), root
