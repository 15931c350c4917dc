"""The benchmark's own tests. They are not under `tests/`, so the
repo's tier-1 command does not collect them; run them by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


import pytest  # noqa: E402


@pytest.fixture(scope="session")
def stand_in_cell():
    """What the `reference_check` functions read of a `Cell`, for a
    checker driven without a trainer: `stand_in_cell(work, config,
    config_path, data_dir, env)`. ``root`` is the checkout whose
    `cell.REF_CACHE` the child keeps its programs in: a directory of the
    test's own starts cold."""
    from types import SimpleNamespace

    from benchmark.harness.cell import Cell

    def make(work, config, config_path, data_dir, env, root=ROOT):
        cell = SimpleNamespace(
            root=str(root), work=str(work), config=config,
            config_path=str(config_path), data_dir=str(data_dir),
            rehearse=True, env=env, child_env=lambda: dict(env))
        cell.reference_env = lambda: Cell.reference_env(cell)
        return cell
    return make
