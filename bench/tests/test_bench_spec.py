"""``BENCHMARK.json`` and the files it names: names, units, keys and the
file each name is found by."""
import json
import re

import pytest

import harness
from bench_testkit import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BM = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51


def test_names_and_units_charset():
    names = [c["name"] for c in BM["configs"]] \
        + [w["name"] for w in BM["workloads"]] \
        + [m["name"] for m in BM["end_to_end"] + BM["per_layer"]] \
        + [w["traffic"] for w in BM["workloads"]] \
        + [k for c in BM["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)


def test_entries_have_only_their_keys():
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BM["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    for m in BM["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_resolves_to_its_files():
    for w in BM["workloads"]:
        cell = harness.resolve(ROOT, w["name"])
        assert harness.load_plugin(BENCH, "drivers", cell.traffic["driver"])
        for prog in cell.config["programs"]:
            assert harness.load_plugin(BENCH, "reference", prog)
            assert prog in cell.config["limits"], prog
        for m in cell.end_to_end + cell.per_layer:
            assert harness.load_plugin(BENCH, "metrics", m["name"])
        assert {m["name"] for m in cell.end_to_end} > {"setup_s"}
        assert cell.per_layer


def test_config_shapes_are_the_programs():
    """Each configuration's arrays have the program's own shapes and
    dtypes (``make_data``), at every row count it runs."""
    import numpy as np

    import datagen
    from repro.core.workloads import get_workload

    pool = __import__("concurrent.futures").futures.ThreadPoolExecutor(2)
    for c in BM["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        for prog, spec in cfg["programs"].items():
            rows = min(spec["rows"])
            mine = datagen.bucket_data(prog, spec, rows, 0, pool)
            theirs = get_workload(prog).make_data(rows,
                                                  np.random.default_rng(0))
            assert spec["combine"] == get_workload(prog).combine
            for m, t in zip(mine, theirs):
                assert sorted(m) == sorted(t), prog
                for k in t:
                    assert m[k].dtype == t[k].dtype, (prog, k)
                    assert m[k].shape[1:] == t[k].shape[1:], (prog, k)
    pool.shutdown()


def test_unknown_device_kind_raises():
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.peaks_for(BENCH, "TPU v99")
    assert harness.peaks_for(BENCH, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
