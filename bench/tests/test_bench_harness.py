"""The harness end to end on the CPU, on a tiny cell added as files only:
it finds the new configuration and traffic mix, decides ``correct``
against the plain reference, and comes out not correct when the timed
path is broken underneath."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import harness
from bench_testkit import BENCH, ROOT, tiny_copy


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("bench"))


def run(root, seed=2 ** 31 + 7, seconds=1.0, trace=False):
    return harness.run_cell(root, "tiny.mix", seed, seconds, trace,
                            t_process=time.perf_counter(), platform=None)


def test_cell_added_as_files_runs_correct(checkout):
    line = run(checkout)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 8
    assert set(line["metrics"]) == {"throughput_rps", "latency_p95_ms",
                                    "setup_s"}
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) <= {"vecadd", "mvmult", "scalarprod",
                                   "covariance"}
    json.dumps(line)


def test_traced_run_reports_program_metrics(checkout, monkeypatch):
    # a profiled sub-window that fits the tiny window
    monkeypatch.setattr(harness, "TRACE_AFTER_S", 0.2)
    monkeypatch.setattr(harness, "TRACE_FOR_S", 0.3)
    line = run(checkout, trace=True)
    assert line["correct"], line["checks"]
    m = line["metrics"]
    # no device plane on the CPU: device metrics are absent, not zero
    assert "device_idle_share" not in m and "busy_s" not in line["device"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["exec_gb_s"]["value"] > 0
    assert 0 < m["coordinator_share"]["value"] < 1


def _patch_dispatch(monkeypatch, alter):
    from repro.core.backends.host_pipelined import PipelinedHostBackend

    orig = PipelinedHostBackend.dispatch

    def broken(self, ctx, config):
        return alter(orig(self, ctx, config))

    monkeypatch.setattr(PipelinedHostBackend, "dispatch", broken)


def test_half_the_calls_left_out_is_not_correct(checkout, monkeypatch):
    _patch_dispatch(monkeypatch, lambda outs: outs[: max(1, len(outs) // 2)]
                    if len(outs) > 1 else [o[: o.shape[0] // 2] for o in outs])
    line = run(checkout)
    assert not line["correct"]
    assert any(c["value"] == "inf" for c in line["checks"].values())


def test_an_altered_answer_is_not_correct(checkout, monkeypatch):
    def alter(outs):
        first = np.asarray(outs[0]).copy()
        first.reshape(-1)[0] += 1.0
        return [first] + list(outs[1:])

    _patch_dispatch(monkeypatch, alter)
    line = run(checkout)
    assert not line["correct"]


def test_command_fails_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "stream-large.warm", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not 'tpu'" in out.stderr


def test_command_fails_without_the_program(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    import shutil
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".work",
                                                  "__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "stream-large.warm",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
