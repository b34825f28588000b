"""Fleet serving: the tenant-sharding router over N spawn-isolated
worker processes.  Pure tests cover the sharding function and the
cross-process telemetry/metrics merge (worker labels, deterministic
ordering, no input mutation); real-process tests cover end-to-end
serving with tenant→worker consistency, model refresh acks, SIGKILL →
respawn → requeue, and shutdown (drain, idempotent close, no orphans)."""
import multiprocessing
import os
import signal

import pytest

from repro.launch.stats import render
from repro.serving import (FleetRouter, WorkerConfig, fleet_summary,
                           make_trace, merge_metrics, merge_samples,
                           shard_for)
from repro.serving.telemetry import TelemetrySample


def _sample(seq, worker=None, tenant="tenant-0", retire=None, status="ok",
            cache_hit=False, refined=False):
    return TelemetrySample(
        seq=seq, tenant=tenant, workload="vecadd", key="vecadd",
        backend="host-sync", partitions=1, tasks=1, cache_hit=cache_hit,
        predicted_s=None, measured_s=0.01, rel_error=None, status=status,
        refined=refined, t_retire_s=retire, worker=worker)


def _fleet_children():
    return [p for p in multiprocessing.active_children()
            if p.name.startswith("fleet-")]


# -- sharding -----------------------------------------------------------------


def test_shard_for_is_stable_and_in_range():
    for n in (1, 2, 3, 4, 7):
        for i in range(16):
            tenant = f"tenant-{i}"
            slot = shard_for(tenant, n)
            assert 0 <= slot < n
            # CRC32, not hash(): the mapping must agree between router,
            # respawned workers, and a fresh interpreter
            assert slot == shard_for(tenant, n)
    # the 8-tenant default exists because it actually uses both slots
    assert {shard_for(f"tenant-{i}", 2) for i in range(8)} == {0, 1}


# -- telemetry merge ----------------------------------------------------------


def test_merge_samples_labels_orders_and_never_mutates():
    w0 = [_sample(0, retire=2.0), _sample(1, retire=None)]
    w1 = [_sample(0, retire=1.0), _sample(1, retire=2.0)]
    merged = merge_samples({"w0": w0, "w1": w1})

    assert [s.worker for s in merged].count("w0") == 2
    assert all(s.worker in ("w0", "w1") for s in merged)
    # inputs keep their unset worker field — merge copies, never mutates
    assert all(s.worker is None for s in w0 + w1)

    # retire-time order, worker label breaking the 2.0 tie, and the
    # never-retired sample (failed before dispatch) sorting last
    assert [(s.worker, s.seq) for s in merged] == [
        ("w1", 0), ("w0", 0), ("w1", 1), ("w0", 1)]

    # deterministic regardless of dict insertion order
    again = merge_samples({"w1": w1, "w0": w0})
    assert [(s.worker, s.seq, s.t_retire_s) for s in again] \
        == [(s.worker, s.seq, s.t_retire_s) for s in merged]


def test_worker_field_roundtrips_and_stays_backwards_compatible():
    s = _sample(3, worker="w2")
    assert TelemetrySample.from_json(s.to_json()).worker == "w2"
    # pre-fleet JSONL (no worker key) still loads; unknown keys filter
    legacy = {k: v for k, v in s.to_json().items() if k != "worker"}
    legacy["some_future_field"] = 1
    assert TelemetrySample.from_json(legacy).worker is None


def test_merge_metrics_labels_series_and_sorts_deterministically():
    fam = {"type": "counter",
           "values": [{"labels": {"namespace": "shared"}, "value": 2}]}
    merged = merge_metrics({"w1": {"serving.cache.hit": fam},
                            "w0": {"serving.cache.hit": fam},
                            "w2": None})        # died before the goodbye
    series = merged["serving.cache.hit"]["values"]
    assert [e["labels"] for e in series] == [
        {"namespace": "shared", "worker": "w0"},
        {"namespace": "shared", "worker": "w1"}]
    assert merged["serving.cache.hit"]["type"] == "counter"

    # the stats renderer consumes the merged snapshot unchanged
    report = render([_sample(0, worker="w0", retire=1.0)], merged)
    assert "worker=w0" in report

    with pytest.raises(ValueError, match="conflicting types"):
        merge_metrics({"w0": {"m": {"type": "counter", "values": []}},
                       "w1": {"m": {"type": "gauge", "values": []}}})


def test_fleet_summary_breaks_down_per_worker():
    samples = merge_samples({
        "w0": [_sample(0, retire=1.0, cache_hit=True),
               _sample(1, retire=2.0, status="failed")],
        "w1": [_sample(0, retire=1.5, refined=True)]})
    s = fleet_summary(samples)
    assert s["requests"] == 3
    assert s["per_worker"] == {
        "w0": {"requests": 2, "cache_hits": 1, "refinements": 0,
               "failed": 1},
        "w1": {"requests": 1, "cache_hits": 0, "refinements": 1,
               "failed": 0}}


# -- real worker processes ----------------------------------------------------


def test_fleet_end_to_end_shards_refreshes_and_shuts_down(tmp_path):
    """2 real workers, 8 requests over 8 tenants: every result terminal
    and served by the worker its tenant hashes to; refresh acks from
    every worker; close() drains the goodbye metrics, is idempotent, and
    leaves no child processes behind."""
    reqs = make_trace(["vecadd"], occurrences=8, tenants=8, scale_index=0)
    jsonl = tmp_path / "fleet.jsonl"
    with FleetRouter(2, worker=WorkerConfig(model="heuristic"),
                     telemetry_path=str(jsonl)) as fr:
        fr.submit_all(reqs)
        results = fr.run()

        assert len(results) == len(reqs)
        workers_used = set()
        for r in results:
            assert r["status"] in ("served", "degraded")
            s = TelemetrySample.from_json(r["sample"])
            assert s.worker == f"w{shard_for(s.tenant, 2)}"
            workers_used.add(s.worker)
        assert workers_used == {"w0", "w1"}

        tags = fr.refresh_model("heuristic")
        assert set(tags) == {"w0", "w1"}
        assert all(tag == "heuristic" for tag in tags.values())

    assert fr.closed
    fr.close()                                   # idempotent
    assert _fleet_children() == []

    summary = fr.summary()
    assert summary["requests"] == len(reqs)
    assert summary["worker_deaths"] == 0
    assert set(summary["per_worker"]) == {"w0", "w1"}

    # goodbye handshake shipped every worker's metrics; the merge stamps
    # each series with its worker label
    snap = fr.metrics_snapshot()
    assert snap
    for fam in snap.values():
        assert all(e["labels"]["worker"] in ("w0", "w1")
                   for e in fam["values"])

    # the merged fleet JSONL landed on disk, one line per request
    assert sum(1 for _ in open(jsonl)) == len(reqs)


def test_fleet_sigkill_respawns_and_every_request_terminates():
    """SIGKILL a worker between batches: the next run() detects the
    death, respawns the slot, requeues its un-acked work, and every
    admitted request still reaches a terminal status."""
    first = make_trace(["vecadd"], occurrences=4, tenants=8, scale_index=0)
    second = make_trace(["vecadd"], occurrences=8, tenants=8,
                        scale_index=0, seed=1)
    with FleetRouter(2, worker=WorkerConfig(model="heuristic")) as fr:
        fr.submit_all(first)
        assert len(fr.run()) == len(first)

        victim = fr._slots[fr.shard_for("tenant-0")]
        os.kill(victim.pid, signal.SIGKILL)
        victim.proc.join(10)
        assert not victim.proc.is_alive()

        fr.submit_all(second)
        results = fr.run()

        assert len(results) == len(second)
        assert all(r["status"] in ("served", "degraded") for r in results)
        assert fr.stats["worker_deaths"] == 1
        assert fr.stats["worker_respawns"] == 1
        assert fr.stats["requeued_requests"] >= 1
    assert _fleet_children() == []
    assert fr.summary()["requests"] == len(first) + len(second)


def test_fleet_respawn_budget_exhaustion_fails_terminally_and_closes():
    """max_respawns=0: a SIGKILL'd slot is abandoned instead of
    respawned and its un-acked work fails terminally with synthetic
    samples, while the healthy slot keeps serving its own requests.
    The abandoned slot's queues are close()d, so the remaining collect
    iterations and close() must tolerate them (the closed-Queue
    ValueError path) — every admitted request still reaches a terminal
    status and shutdown leaves no orphans."""
    reqs = make_trace(["vecadd"], occurrences=8, tenants=8, scale_index=0)
    with FleetRouter(2, worker=WorkerConfig(model="heuristic"),
                     max_respawns=0) as fr:
        victim_i = fr.shard_for("tenant-0")
        victim = fr._slots[victim_i]
        os.kill(victim.pid, signal.SIGKILL)
        victim.proc.join(10)
        assert not victim.proc.is_alive()

        fr.submit_all(reqs)
        results = fr.run()

        assert len(results) == len(reqs)
        failed, served = [], []
        for r in results:
            s = TelemetrySample.from_json(r["sample"])
            if shard_for(s.tenant, 2) == victim_i:
                failed.append(r)
                assert r["status"] == "failed"
                assert "respawn budget" in r["error"]
                assert s.status == "failed"
                assert s.worker == f"w{victim_i}"
            else:
                served.append(r)
                assert r["status"] in ("served", "degraded")
        assert failed and served     # both slots actually had work
        assert fr.stats["worker_deaths"] == 1
        assert fr.stats["abandoned_slots"] == 1
        assert fr.stats["worker_respawns"] == 0

        # new work for an abandoned seat fails at admission, healthy
        # tenants are unaffected
        more = make_trace(["vecadd"], occurrences=4, tenants=8,
                          scale_index=0, seed=1)
        fr.submit_all(more)
        again = fr.run()
        assert len(again) == len(more)
        for r in again:
            s = TelemetrySample.from_json(r["sample"])
            if shard_for(s.tenant, 2) == victim_i:
                assert r["status"] == "failed"
            else:
                assert r["status"] in ("served", "degraded")
    assert fr.closed
    fr.close()                                   # idempotent
    assert _fleet_children() == []
    assert fr.summary()["requests"] == len(reqs) + len(more)


# -- placement: the platform a worker was given, one chip per slot -----------


def _environ_of(pid: int) -> dict:
    with open(f"/proc/{pid}/environ", "rb") as f:
        items = f.read().decode().split("\0")
    return dict(item.split("=", 1) for item in items if "=" in item)


def test_fleet_workers_keep_their_platform_and_get_one_chip_each():
    """No code under src/ picks JAX's platform for a process: each worker
    serves on the platform it was given (here the test run's CPU), and
    the router pins every slot's child environment to a chip of its own
    without leaking the pin into its own environment."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    for root, _, files in os.walk(src):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    assert "JAX_PLATFORMS" not in f.read(), name

    given = os.environ["JAX_PLATFORMS"]
    with FleetRouter(2, worker=WorkerConfig(model="heuristic")) as fr:
        devices = fr.summary()["devices"]
        envs = [_environ_of(slot.pid) for slot in fr._slots]
    assert {d["platform"] for d in devices.values()} == {given}
    assert [e["JAX_PLATFORMS"] for e in envs] == [given, given]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 2
    assert "TPU_VISIBLE_CHIPS" not in os.environ
    assert _fleet_children() == []


def test_fleet_serve_parent_never_starts_a_jax_backend(tmp_path):
    """Every chip belongs to a worker: ``fleet_serve`` resolves the
    published artifact from its manifest and ships the id, so the
    serving parent never initializes a backend.  An empty registry is an
    error here, not a bootstrap."""
    import json
    import subprocess
    import sys

    import numpy as np

    from repro.core.features import RAW_FEATURE_NAMES, config_features
    from repro.core.modeling import TreeRegressor
    from repro.core.modeling.registry import ModelRegistry
    from repro.launch.serve import fleet_serve

    with pytest.raises(FileNotFoundError, match="no 'latest' artifact"):
        fleet_serve(["vecadd"], model_dir=str(tmp_path / "empty"),
                    verbose=False)

    rng = np.random.default_rng(0)
    X = np.stack([np.concatenate([rng.uniform(0.5, 2, len(RAW_FEATURE_NAMES)),
                                  config_features(2 ** (i % 3), 2 ** (i % 5))])
                  for i in range(40)])
    registry = ModelRegistry(tmp_path / "models")
    artifact = registry.publish(TreeRegressor.train(X, rng.uniform(1, 2, 40),
                                                    depth=3))
    code = """
import json, sys
from jax._src import xla_bridge
from repro.launch.serve import fleet_serve
if __name__ == "__main__":
    s = fleet_serve(["vecadd"], n_requests=4, worker_procs=1,
                    model_dir=sys.argv[1], verbose=False)
    print(json.dumps({"artifact": s["model"]["artifact_id"],
                      "by_status": s["by_status"],
                      "backends": xla_bridge.backends_are_initialized()}))
"""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    r = subprocess.run([sys.executable, "-c", code, str(tmp_path / "models")],
                       env=dict(os.environ, PYTHONPATH=src),
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out == {"artifact": artifact, "by_status": {"ok": 4},
                   "backends": False}
