"""CPU tests of the benchmark harness, at sizes a test run holds.

    PYTHONPATH=src python -m pytest -q bench/tests
"""

import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

CELL = "products-single-1chip"


@pytest.fixture
def tiny_setup():
    """``bench.run.load_cell(CELL)`` with the graph cut to ``nodes`` and a
    peak table for the CPU: everything else as the cell runs it."""
    from bench import run as R

    def make(nodes: int = 2048, cell: str = CELL):
        setup = copy.deepcopy(R.load_cell(cell))
        setup["config"]["graph"]["nodes"] = nodes
        setup["config"]["name"] = f"{setup['config']['name']}-{nodes}"
        setup["peaks"] = {"devices": {"cpu": {"bf16_flops_per_s": 1e12,
                                              "hbm_bytes_per_s": 1e11}}}
        return setup
    return make
