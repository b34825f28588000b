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


@pytest.mark.parametrize("program", ["scalarprod", "mri-gridding"])
def test_sum_programs_compare_over_their_terms_magnitudes(program):
    """A ``sum`` program's answer can cancel to near zero: its gap is
    taken over the reference on its inputs' magnitudes, so float32
    rounding reads the same small number whatever the draw, and an
    answer off by a part in a thousand of its terms still fails."""
    from repro.core.workloads import get_workload

    wl = get_workload(program)
    c, s = wl.make_data(256, np.random.default_rng(5))
    key = "a" if program == "scalarprod" else "val"
    # cancel the reference: the second half is the first half negated
    half = c[key][:128].copy()
    c[key][128:] = -half
    if program == "scalarprod":
        c["b"][128:] = c["b"][:128]
    else:
        c["idx"][128:] = c["idx"][:128]
    outs = [np.asarray(jax.jit(wl.kernel)(c, s))]
    ref = _ref(program)
    want = check.expected(ref, REFERENCE, "sum", c, s, (1, 1))
    scale = check.expected(ref, REFERENCE, "sum", check.magnitudes(c),
                           check.magnitudes(s), (1, 1))
    assert np.max(np.abs(want[0])) < 1e-6 * np.max(scale[0])
    assert check.request_gap(ref, "sum", c, s, (1, 1), outs) < 1e-6
    off = [o + 1e-3 * np.max(scale[0]) for o in outs]
    assert check.request_gap(ref, "sum", c, s, (1, 1), off) \
        == pytest.approx(1e-3, rel=1e-3)
