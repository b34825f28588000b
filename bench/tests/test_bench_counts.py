"""Each count file against FLOPs and bytes worked out by hand."""
import pytest

import harness
from bench_testkit import BENCH

# program -> (rows, flops, bytes) for one kernel call on that many rows
HAND = {
    "vecadd": (1, 256, 3 * 1024),                 # 256 adds; a, b, out
    "transpose": (2, 0, 2 * 2 * 64 * 64 * 4),     # read + write 2 tiles
    "prefix": (1, 2047, 2 * 2048 * 4),
    "jacobi-2d": (1, 2 * 5 * 2304, 2 * 2304 * 4),
    "lbm": (1, 44 * 1024, 2 * 9 * 1024 * 4),
    "mvmult": (2, 2 * 2 * 768, (2 * 768 + 768 + 2) * 4),
}


@pytest.mark.parametrize("program", sorted(HAND))
def test_counts_match_hand(program):
    rows, flops, nbytes = HAND[program]
    mod = harness.load_plugin(BENCH, "counts", program)
    assert mod.counts(rows) == (float(flops), float(nbytes))


def test_every_count_file_is_checked():
    assert sorted(p.stem for p in (BENCH / "counts").glob("*.py")) \
        == sorted(HAND)
