"""The copied traffic generator and the data maker: the same seed gives
the same stream and the same arrays; another seed the same mix in
another order."""
import collections
import itertools
import json
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import datagen
import trafficgen
from bench_testkit import ROOT

SUITE = json.loads((ROOT / "bench/configs/suite39.json").read_text())
COLD = json.loads((ROOT / "bench/traffic/uniform-cold.json").read_text())


def take(seed, n, config=SUITE, traffic=COLD):
    return list(itertools.islice(
        trafficgen.request_items(config, traffic, seed), n))


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, 2 ** 40 + 3, -7])
def test_stream_is_deterministic(seed):
    assert take(seed, 600) == take(seed, 600)


def test_seeds_send_the_same_mix_in_another_order():
    n = 2 * COLD["block"]
    a, b = take(1, n), take(2 ** 31 + 1, n)
    key = lambda it: (it.program, it.tenant)  # noqa: E731
    assert [key(x) for x in a] != [key(x) for x in b]
    assert collections.Counter(x.program for x in a) \
        == collections.Counter(x.program for x in b)
    assert collections.Counter(x.tenant for x in a) \
        == collections.Counter(x.tenant for x in b)


def test_programs_tenants_and_datasets_are_uniform():
    items = take(3, 2 * COLD["block"])
    names = sorted(SUITE["programs"])
    counts = collections.Counter(x.program for x in items)
    assert set(counts) == set(names) and len(set(counts.values())) == 1
    tenants = collections.Counter(x.tenant for x in items)
    assert len(tenants) == COLD["tenants"] and len(set(tenants.values())) == 1
    for prog in names[:5]:
        rows = collections.Counter(x.rows for x in items if x.program == prog)
        assert sorted(rows) == sorted(SUITE["programs"][prog]["rows"])
        assert max(rows.values()) - min(rows.values()) <= trafficgen.DECK


@pytest.mark.parametrize("seed", [4, 2 ** 31 + 17])
def test_warm4_blocks_hold_every_program_4_and_every_tenant_39_times(seed):
    """``suite39.warm``'s mix: each block of 156 sends each of the 39
    programs 4 times and each of the 4 tenants 39 times, whatever the
    seed, so that a run's work does not hang on its seed."""
    warm = json.loads((ROOT / "bench/traffic/uniform-warm4.json").read_text())
    items = take(seed, 3 * warm["block"], traffic=warm)
    for b in range(3):
        block = items[b * warm["block"]:(b + 1) * warm["block"]]
        assert set(collections.Counter(x.program for x in block).values()) \
            == {4}
        tenants = collections.Counter(x.tenant for x in block)
        assert len(tenants) == 4 and set(tenants.values()) == {39}
    assert abs(sum(x.check for x in items)
               - len(items) / warm["check_every"]) < 1


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9])
def test_checks_spread_over_the_window(seed):
    every = COLD["check_every"]
    items = take(seed, 40 * every)
    marked = [i for i, x in enumerate(items) if x.check]
    assert len(marked) == 40
    assert set(np.diff(marked)) == {every}
    assert marked[-1] >= len(items) - every
    phases = {take(s, every).index(next(x for x in take(s, every) if x.check))
              for s in range(12)}
    assert len(phases) > 1


def test_apportion_sums_and_follows_probs():
    probs = 1.0 / np.arange(1, 40) ** 1.1
    probs /= probs.sum()
    c = trafficgen.apportion(probs, 512)
    assert c.sum() == 512 and np.all(np.abs(c - probs * 512) < 1)


def test_arrays_depend_on_seed_not_threads():
    spec = {"chunked": {"a": {"shape": [256], "dist": "normal"},
                        "i": {"shape": [4], "dist": ["randint", 0, 9]}},
            "shared": {"v": {"shape": [7], "dist": ["uniform", 2, 3]}}}
    rows = 5000   # a few blocks of the generator
    with ThreadPoolExecutor(1) as one, ThreadPoolExecutor(8) as many:
        a = datagen.bucket_data("x", spec, rows, 11, one)
        b = datagen.bucket_data("x", spec, rows, 11, many)
        c = datagen.bucket_data("x", spec, rows, 12, many)
    for k in spec["chunked"]:
        assert np.array_equal(a[0][k], b[0][k])
        assert a[0][k].shape[0] == rows + trafficgen.pad_rows(rows)
    assert np.array_equal(a[1]["v"], b[1]["v"])
    assert not np.array_equal(a[0]["a"], c[0]["a"])
    assert a[0]["i"].dtype == np.int32 and a[0]["i"].max() < 9
    assert np.all((a[1]["v"] >= 2) & (a[1]["v"] < 3))
