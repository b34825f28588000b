"""The engine seam: a configuration names its engine file by ``kind``,
and an engine whose chips belong to another process needs nothing of
the harness but new files."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

import engine_api
import harness
from bench_testkit import BENCH, ROOT, TINY_CONFIG, tiny_copy

BM = json.loads((ROOT / "BENCHMARK.json").read_text())


def add_config(root, name, engine_kind, metrics=None):
    """The tiny configuration again under ``name`` with ``engine.kind``,
    and a cell ``<name>.mix`` on the tiny traffic reading ``metrics``
    (every per-layer metric when None), as new files and entries."""
    config = json.loads(json.dumps(TINY_CONFIG))
    config["name"] = name
    config["engine"]["kind"] = engine_kind
    (root / f"bench/configs/{name}.json").write_text(json.dumps(config))
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": name, "source": config["source"],
                          "file": f"bench/configs/{name}.json",
                          "reduced": ["rows"], "why": "test"})
    bm["workloads"].append({"name": f"{name}.mix", "config": name,
                            "traffic": "tiny-mix", "chips": 1,
                            "why": "test"})
    for m in bm["per_layer"]:
        if metrics is None or m["name"] in metrics:
            m["workloads"].append(f"{name}.mix")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))


@pytest.mark.parametrize("config", [c["file"] for c in BM["configs"]]
                         + [None])
def test_a_configuration_without_kind_runs_the_concurrent_engine(config):
    """Today's configurations name no engine: they keep the engine they
    had, ``bench/engines/concurrent.py``, which holds its own chips."""
    cfg = (json.loads((ROOT / config).read_text()) if config
           else {"engine": {"model": "heuristic"}})
    assert "kind" not in cfg["engine"]
    assert engine_api.kind_of(cfg) == "concurrent"
    mod = harness.load_plugin(BENCH, "engines", engine_api.kind_of(cfg))
    assert mod.OWN_CHIPS
    for w in BM["workloads"]:
        assert harness.resolve(ROOT, w["name"]).engine_kind == "concurrent"


def test_an_unknown_kind_exits_non_zero_with_no_result(tmp_path):
    """A configuration that names an engine file the checkout lacks is
    refused before any chip is asked for: the command prints no line."""
    root = tiny_copy(tmp_path)
    (root / "src").symlink_to(ROOT / "src")
    add_config(root, "nokind", "nosuch")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nokind.mix",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "engines/nosuch.py" in out.stderr


# runs the harness in a process of its own, which has not touched JAX
HARNESS = textwrap.dedent("""
    import json, os, sys, time
    root = sys.argv[1]
    sys.path[:0] = [root + "/bench/lib", root + "/src"]
    import harness
    from jax._src import xla_bridge
    harness.TRACE_AFTER_S, harness.TRACE_FOR_S = 0.2, 0.4
    cr = harness.CellRun(root, "inchild.mix", 2 ** 31 + 13, 1.5, True,
                         t_process=time.perf_counter(), platform=None)
    cr.setup()
    cr.window()
    engine, got = cr.engine, {}
    collect = engine.collect

    def collected():
        got["collected"] = collect()
        return got["collected"]

    engine.collect = collected
    line = cr.finish()
    print(json.dumps({
        "line": line, "harness_pid": os.getpid(),
        "child_pid": engine.proc.pid,
        "child_exit": engine.proc.returncode,
        "child_peaks": got["collected"].memory_peaks,
        "span_procs": sorted({s.proc for s in cr.run.spans}),
        "span_tids": sorted({s.tid[0] for s in cr.run.spans}),
        "trace_procs": [t.proc for t in got["collected"].traces],
        "backends": xla_bridge.backends_are_initialized()}))
""")


def test_an_engine_in_a_child_process_added_as_files_only(tmp_path):
    """A checkout gains an engine file, a configuration naming it and a
    cell, and no file of the benchmark changes.  The engine serves from
    a spawned child: the harness's process never starts a JAX backend,
    the run is correct against the reference, and the spans that the
    per-layer metrics read and the memory peak in the line are the
    child's, each span tagged with its process."""
    root = tiny_copy(tmp_path)
    (root / "src").symlink_to(ROOT / "src")
    shutil.copy(BENCH / "tests/engines/child.py",
                root / "bench/engines/child.py")
    # tuned_speedup sets up on the harness process's own chips
    add_config(root, "inchild", "child",
               metrics=[m["name"] for m in BM["per_layer"]
                        if m["name"] != "tuned_speedup"])
    for path in BENCH.rglob("*"):
        if path.is_file() and not {".jax_cache", ".work", "__pycache__"} \
                & set(path.relative_to(BENCH).parts):
            copy = root / "bench" / path.relative_to(BENCH)
            assert copy.read_bytes() == path.read_bytes(), path

    out = subprocess.run(
        [sys.executable, "-c", HARNESS, str(root)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": ""})
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    line = got["line"]
    assert got["backends"] is False
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 8
    assert got["child_pid"] != got["harness_pid"]
    assert got["child_exit"] == 0
    assert got["span_procs"] == got["span_tids"] == [got["child_pid"]]
    assert got["trace_procs"] == [got["child_pid"]]
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                              "memory_peak_bytes": max(got["child_peaks"])}
    m = line["metrics"]
    assert 0 < m["coordinator_share"]["value"] < 1
    assert m["exec_gb_s"]["value"] > 0
    assert m["compiles_in_window"]["value"] == 0


def test_a_metric_that_sets_up_in_process_refuses_a_child_engine(tmp_path):
    """``tuned_speedup`` times the executor in the harness's process in
    set-up; in a cell whose chips belong to another process it would
    time a chip that serves nothing, so the traced run is refused."""
    root = tiny_copy(tmp_path)
    shutil.copy(BENCH / "tests/engines/child.py",
                root / "bench/engines/child.py")
    add_config(root, "inchild", "child")
    cr = harness.CellRun(root, "inchild.mix", 1, 1.0, True,
                         t_process=0.0, platform=None)
    with pytest.raises(harness.BenchError, match="tuned_speedup"):
        cr.setup()
    assert cr.engine is None          # refused before the child started
