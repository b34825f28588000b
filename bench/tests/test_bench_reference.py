"""The plain references against the program's kernels on the CPU, and the
control: the same references one precision step down must read far above
the program, for every program whose answer is not exact."""
import importlib.util

import jax
import numpy as np
import pytest

import check
from bench_testkit import BENCH
from precision import REFERENCE, LowerOnDevice

PROGRAMS = sorted(p.stem for p in (BENCH / "reference").glob("*.py"))
# integer answers: both precisions give them exactly
EXACT = {"bfs", "histo"}


def _ref(name):
    spec = importlib.util.spec_from_file_location(
        f"ref_{name}", BENCH / "reference" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def lower():
    return LowerOnDevice()


def test_one_reference_per_program():
    from repro.core.workloads import list_workloads
    assert PROGRAMS == list_workloads()


@pytest.mark.parametrize("name", PROGRAMS)
def test_reference_matches_program_and_control_does_not(name, lower):
    from repro.core.workloads import get_workload

    wl = get_workload(name)
    c, s = wl.make_data(wl.datasets[1], np.random.default_rng(3))
    with jax.default_matmul_precision("highest"):
        got = [np.asarray(jax.jit(wl.kernel)(c, s))]
    ref = _ref(name)
    want = check.expected(ref, REFERENCE, "concat", c, s, (1, 1))
    sound = check.gap(got, want)
    assert sound < 1e-5, sound
    control = check.gap(
        check.expected(ref, lower, "concat", c, s, (1, 1)), want)
    if name in EXACT:
        assert control == 0.0
    else:
        assert control > 1e3 * max(sound, 1e-7), (sound, control)


@pytest.mark.parametrize("split", [(1, 1), (2, 3), (4, 8)])
def test_local_programs_compare_per_call(split):
    from repro.core.workloads import get_workload

    wl = get_workload("covariance")
    c, s = wl.make_data(512, np.random.default_rng(4))
    outs = [np.asarray(jax.jit(wl.kernel)(
        {k: v[lo:hi] for k, v in c.items()}, s))
        for lo, hi in check.call_rows(512, *split)]
    assert check.request_gap(_ref("covariance"), "local", c, s, split,
                             outs) < 1e-5
    # the same outputs judged as the unsplit answer are wrong
    if split != (1, 1):
        assert check.request_gap(_ref("covariance"), "local", c, s, (1, 1),
                                 outs) == float("inf")


def test_call_rows_is_array_split():
    rows = 1000
    got = check.call_rows(rows, 3, 7)
    idx = np.arange(rows)
    want = [(int(p[0]), int(p[-1]) + 1) for t in np.array_split(idx, 7)
            for p in np.array_split(t, 3)]
    assert got == want
