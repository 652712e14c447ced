"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
repository root. They run on the CPU at tiny sizes (``data/tiny.json``),
where the Pallas kernels run interpreted."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY = json.loads((Path(__file__).parent / "data" / "tiny.json").read_text())


@pytest.fixture(scope="session")
def harness():
    """``bench.run`` with the program imported and no chip required."""
    from bench import run
    run.prepare(require_tpu=False)
    return run


@pytest.fixture
def tiny_cell():
    """A tiny cell of one workload kind, outside ``BENCHMARK.json``."""
    from bench import discover

    def make(kind: str) -> "discover.Cell":
        return discover.Cell(name=f"tiny-{kind}", chips=1,
                             config=TINY["configs"]["tiny"],
                             traffic=dict(TINY["traffic"][kind]),
                             kind=discover.kind(kind), end_to_end=[],
                             per_layer=[], readers={})
    return make


@pytest.fixture
def search_cell():
    """The committed search cell, whose graph the search improves on."""
    from bench import discover
    return discover.resolve("search-bl260c")
