import os
import sys

import pytest

# tests must see the real single CPU device (the 512-device flag is only
# ever set inside launch/dryrun.py's own process); the chip is reached
# only through chip_smoke.py
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# tests never write JAX's persistent compilation cache, and neither do
# the fleet workers and subprocesses they start (which inherit this)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


@pytest.fixture(autouse=True, scope="session")
def fast_profile_defaults():
    """Shrink profiling defaults so test runs stay under budget.

    Any test that profiles through ``dataset.grid_for`` — fast or slow
    tier — gets a 4x8 config grid instead of the paper's 32x64 sweep.
    Set REPRO_FULL_PROFILE=1 to restore the full grid; the real sweep
    lives in ``benchmarks/run.py``, which does not run under pytest and
    is unaffected.
    """
    if os.environ.get("REPRO_FULL_PROFILE"):
        yield
        return
    # patch the real module, not the repro.core.dataset shim: the call
    # sites (profile_sample) resolve grid_for in the modeling namespace
    from repro.core.modeling import dataset

    orig_grid_for = dataset.grid_for

    def small_grid(n_rows, max_partitions=4, max_tasks=8):
        return orig_grid_for(n_rows, max_partitions, max_tasks)

    dataset.grid_for = small_grid
    try:
        yield
    finally:
        dataset.grid_for = orig_grid_for
