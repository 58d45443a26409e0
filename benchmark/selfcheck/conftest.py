"""The self-checks run by hand: `python -m pytest benchmark/selfcheck -q`.
They are not under `tests/`, so tier-1 does not collect them. They run on
the CPU at tiny sizes; nothing they print is a device number."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for path in (os.path.dirname(BENCH), BENCH, os.path.join(BENCH, "reference")):
    if path not in sys.path:
        sys.path.insert(0, path)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _one_run_per_process():
    """A run of the benchmark is a process of its own; these checks drive
    several in one. The program's books of state rows are process-wide,
    so they are cleared between checks, as `tests/conftest.py` does."""
    yield
    from risingwave_tpu.state.topology import TOPOLOGY
    TOPOLOGY.clear()
