"""Bench-regression gate: compare a freshly produced serving benchmark
JSON against the committed baseline and fail CI on a real regression.

    python benchmarks/check_regression.py FRESH BASELINE [--tolerance 0.25]

Works on all the benchmark artifacts:

  BENCH_serving.json  (``--serve-concurrent``)  gated on
      ``capacity_fraction`` — the engine's speedup normalized by the SAME
      run's measured host parallel-capacity ceiling.  The raw ceiling on
      the shared 2-vCPU CI class drifts ~1.3-2.3x with neighbor load
      (ROADMAP), so raw throughput/speedup would flag the *host*, not the
      code; the fraction cancels the drift.
  BENCH_oracle.json   (``--serve-oracle``)      gated on
      ``mean_regret`` — achieved/oracle runtime ratio, already a ratio of
      two measurements taken on the same box under the same load regime.
  BENCH_model.json    (``--model-eval``)        gated on
      ``model_frac_of_oracle`` (LOO-CV achieved/oracle speedup of the
      trained model) and ``model_vs_heuristic`` (trained model vs the
      zero-training stand-in on the same corpus) — both ratios of
      measurements from one profiled grid, so host drift cancels.
  BENCH_latency.json  (``--serve-trace``)       gated on
      tail-latency / SLO metrics from the virtual-time trace replay:
      ``deadline_slo_violation_rate``, ``fifo_slo_violation_rate`` and
      ``deadline_p95_latency_ms`` (lower is better),
      ``stationary_refinements`` (a baseline of 0 makes this an
      exact-zero gate: contention must never masquerade as drift on a
      stationary trace), and ``deadline_vs_fifo_violation_improvement``
      (higher is better — EDF + shedding must keep beating FIFO).
      These numbers are deterministic given the seed (no wall clock in
      the loop), so even a tight tolerance is noise-free.
  BENCH_resilience.json (``--serve-chaos``)     gated on
      ``chaos_crashes`` (baseline 0 == exact-zero gate),
      ``chaos_terminal_fraction``, ``chaos_failed_fraction`` and
      ``chaos_slo_violation_delta`` from the fault-injected run of the
      real engine under the committed schedule
      (``benchmarks/data/chaos_faults.json``).
  BENCH_fleet.json    (``--serve-fleet``)       gated on
      ``fleet_scaling_fraction`` — N-worker-process speedup normalized
      by min(N, the same run's measured capacity ceiling), the
      multi-process twin of ``capacity_fraction`` — plus two exact-zero
      gates: ``fleet_worker_crashes`` (unplanned worker deaths) and
      ``fleet_kill_lost_requests`` (requests not terminal after the
      SIGKILL + respawn drill), and ``fleet_kill_terminal_fraction``.
      ``ipc_overhead_fraction`` (share of router wall not covered by
      the busiest worker's engine wall — the data-plane tax; lower is
      better) is gated with an absolute-slack cushion (see ABS_SLACK):
      it is a small absolute fraction, so a pure relative tolerance
      would turn measurement noise on a tiny baseline into a red gate.
      Fleet baselines also arm one STRUCTURAL check: fresh
      ``throughput_rps["2"]`` must be strictly above
      ``throughput_rps["1"]`` — adding the second worker process must
      never make the fleet slower, regardless of what the shared host
      does to the absolute numbers (both sides of the comparison ride
      the same box in the same run).

A higher-is-better metric regresses when
``fresh < baseline * (1 - tolerance)``; a lower-is-better one when
``fresh > baseline * (1 + tolerance)``.  The default 25% tolerance is
deliberately loose for the same reason the wall-clock metrics are
ratios: this gate exists to catch code-level regressions (a scheduling
bug halving overlap, a refinement loop converging to junk configs), not
to re-measure the neighbors.  Improvements are reported but never fail.
Missing metrics fail loudly — a silently skipped gate is worse than a
red one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# metric name -> (direction, description); direction is "higher" when
# bigger means healthier, "lower" for latency/violation-style metrics
GATED_METRICS = {
    "capacity_fraction":
        ("higher", "engine speedup / host parallel-capacity ceiling"),
    "mean_regret":
        ("higher", "steady-state achieved/oracle runtime ratio"),
    "model_frac_of_oracle":
        ("higher", "LOO-CV achieved/oracle speedup of the trained model"),
    "model_vs_heuristic":
        ("higher", "trained-model / heuristic achieved speedup on the "
                   "same corpus"),
    "deadline_slo_violation_rate":
        ("lower", "SLO misses (retired late + shed) / deadline-carrying "
                  "requests, deadline policy, bursty trace"),
    "fifo_slo_violation_rate":
        ("lower", "same, fifo policy — the no-admission-control bound"),
    "deadline_p95_latency_ms":
        ("lower", "p95 end-to-end latency, deadline policy, virtual ms"),
    "stationary_refinements":
        ("lower", "drift refinements on a stationary trace (baseline 0 "
                  "== exact-zero gate)"),
    "deadline_vs_fifo_violation_improvement":
        ("higher", "fifo / deadline SLO-violation rate on the same "
                   "trace"),
    "chaos_crashes":
        ("lower", "scheduler crashes under the committed fault schedule "
                  "(baseline 0 == exact-zero gate: the resilience layer "
                  "must NEVER let an injected fault kill the process)"),
    "chaos_terminal_fraction":
        ("higher", "requests reaching a terminal status (served / "
                   "degraded / failed / timeout) under chaos — a lost "
                   "request is a scheduler bug"),
    "chaos_failed_fraction":
        ("lower", "requests individually failed/timed out under chaos "
                  "— deterministic given the committed fault windows"),
    "chaos_slo_violation_delta":
        ("lower", "SLO-violation rate added by the committed faults vs "
                  "the same run fault-free; gate loosely (thread-timing "
                  "noise), it exists to catch retry storms and "
                  "unrecovered breakers"),
    "fleet_scaling_fraction":
        ("higher", "N-worker-process fleet speedup / min(N, measured "
                   "parallel-capacity ceiling) — the same-run "
                   "normalization that cancels shared-host drift"),
    "fleet_worker_crashes":
        ("lower", "UNplanned worker-process deaths across the fleet "
                  "scaling runs (baseline 0 == exact-zero gate; "
                  "injected SIGKILLs are excluded)"),
    "fleet_kill_lost_requests":
        ("lower", "requests that never reached a terminal status after "
                  "a mid-trace SIGKILL + respawn (baseline 0 == "
                  "exact-zero gate: handoff must requeue everything)"),
    "fleet_kill_terminal_fraction":
        ("higher", "admitted requests reaching a terminal status in the "
                   "SIGKILL drill — the fleet twin of "
                   "chaos_terminal_fraction"),
    "ipc_overhead_fraction":
        ("lower", "fleet data-plane tax at max N: router run wall not "
                  "covered by the busiest worker's engine wall, over "
                  "run wall — dispatch + pickling + collection cost"),
}

# metric -> absolute slack added on top of the relative tolerance when
# computing the bound.  For small absolute fractions (an ipc overhead
# baseline of e.g. 0.05) a pure relative band is narrower than the
# run-to-run noise on a shared CI box; the slack keeps the gate about
# code-level regressions (a reintroduced poll loop, a fat wire format)
# instead of scheduler jitter
ABS_SLACK = {
    "ipc_overhead_fraction": 0.15,
}

# context printed next to the verdict but never gated (absolute numbers
# that legitimately drift with the shared host)
INFO_METRICS = ("speedup", "fleet_speedup", "parallel_capacity", "wall_s")


def gate(fresh: dict, baseline: dict, tolerance: float,
         rows: list | None = None) -> list[str]:
    """Returns a list of failure messages (empty == gate passes).

    ``rows``, when given, collects one
    ``{metric, fresh, baseline, bound, verdict, description}`` dict per
    gated metric — the structured form the CI step-summary table is
    rendered from (stdout keeps the full-precision log lines)."""
    shared = [m for m in GATED_METRICS if baseline.get(m) is not None]
    if not shared:
        return [f"baseline has none of the gated metrics "
                f"{sorted(GATED_METRICS)} — wrong file?"]
    failures = []
    for metric in shared:
        direction, desc = GATED_METRICS[metric]
        base = float(baseline[metric])
        if fresh.get(metric) is None:     # absent OR null (e.g. a trace
            # too short to serve every tenant leaves regret undefined)
            failures.append(f"{metric}: missing from fresh results "
                            f"(baseline {base:.3f})")
            if rows is not None:
                rows.append({"metric": metric, "fresh": None,
                             "baseline": base, "bound": None,
                             "verdict": "MISSING", "description": desc})
            continue
        got = float(fresh[metric])
        slack = ABS_SLACK.get(metric, 0.0)
        if direction == "higher":
            bound = base * (1.0 - tolerance) - slack
            bad = got < bound
            kind, rel = "floor", "<"
        else:
            bound = base * (1.0 + tolerance) + slack
            bad = got > bound
            kind, rel = "ceil", ">"
        verdict = "REGRESSION" if bad else "OK"
        print(f"  {metric:38s} fresh={got:9.4f}  baseline={base:9.4f}  "
              f"{kind}={bound:9.4f}  {verdict}   ({desc})")
        if rows is not None:
            rows.append({"metric": metric, "fresh": got, "baseline": base,
                         "bound": bound, "verdict": verdict,
                         "description": f"{kind} ({direction} is better)"})
        if bad:
            failures.append(
                f"{metric}: {got:.4f} {rel} {bound:.4f} "
                f"(baseline {base:.4f} {'-' if direction == 'higher' else '+'}"
                f" {tolerance:.0%})")
    failures += _structural_checks(fresh, baseline, rows)
    for metric in INFO_METRICS:
        if metric in fresh and metric in baseline \
                and isinstance(fresh[metric], (int, float)) \
                and isinstance(baseline[metric], (int, float)):
            print(f"  {metric:20s} fresh={float(fresh[metric]):7.3f}  "
                  f"baseline={float(baseline[metric]):7.3f}  (info only)")
    return failures


def _structural_checks(fresh: dict, baseline: dict,
                       rows: list | None = None) -> list[str]:
    """Same-run shape invariants, armed by the baseline's artifact kind
    rather than a stored number.  Fleet baselines (those carrying
    ``fleet_scaling_fraction``) require the fresh run's 2-worker
    throughput to be STRICTLY above its 1-worker throughput: both sides
    come from the same box in the same run, so shared-host drift
    cancels and any ratio <= 1 means the second process bought nothing
    — a data-plane regression no relative tolerance should forgive."""
    if baseline.get("fleet_scaling_fraction") is None:
        return []
    rps = fresh.get("throughput_rps") or {}
    if not ({"1", "2"} <= set(rps)):
        return []                     # single-worker run: nothing to compare
    ratio = float(rps["2"]) / max(float(rps["1"]), 1e-12)
    bad = ratio <= 1.0
    verdict = "REGRESSION" if bad else "OK"
    print(f"  {'fleet_throughput_1to2':38s} fresh={ratio:9.4f}  "
          f"baseline={1.0:9.4f}  floor={1.0:9.4f}  {verdict}   "
          f"(2-worker rps / 1-worker rps, strict; structural)")
    if rows is not None:
        rows.append({"metric": "fleet_throughput_1to2", "fresh": ratio,
                     "baseline": 1.0, "bound": 1.0, "verdict": verdict,
                     "description": "strict floor (structural)"})
    if bad:
        return [f"fleet_throughput_1to2: {ratio:.4f} <= 1.0 (2-worker "
                f"throughput must be strictly above 1-worker: "
                f"{float(rps['2']):.1f} vs {float(rps['1']):.1f} rps)"]
    return []


def _fmt(v) -> str:
    return f"{v:.4f}" if isinstance(v, float) else str(v)


def write_step_summary(title: str, rows: list, failures: list[str],
                       path: str) -> None:
    """Append a markdown pass/fail table to ``$GITHUB_STEP_SUMMARY`` —
    one header line + one row per gated metric, so a red gate is
    readable from the Actions summary page without opening raw logs."""
    lines = [f"### {title}", ""]
    lines.append("| metric | fresh | baseline | bound | verdict |")
    lines.append("|---|---|---|---|---|")
    for r in rows:
        icon = {"OK": "✅", "REGRESSION": "❌",
                "MISSING": "❓"}.get(r["verdict"], "")
        lines.append(
            f"| `{r['metric']}` "
            f"| {_fmt(r['fresh']) if r['fresh'] is not None else '—'} "
            f"| {_fmt(r['baseline'])} "
            f"| {_fmt(r['bound']) if r['bound'] is not None else '—'} "
            f"| {icon} {r['verdict']} |")
    lines.append("")
    if failures:
        tripped = ", ".join(f"`{f.split(':', 1)[0]}`" for f in failures)
        lines.append(f"**GATE FAILED** — tripped: {tripped}")
    else:
        lines.append("Gate passed.")
    lines.append("")
    with open(path, "a") as f:
        f.write("\n".join(lines) + "\n")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("fresh", help="freshly produced benchmark JSON")
    ap.add_argument("baseline", help="committed baseline JSON")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed fractional drop below baseline "
                         "(default 0.25)")
    args = ap.parse_args()

    with open(args.fresh) as f:
        fresh = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)

    print(f"bench-regression gate: {args.fresh} vs {args.baseline} "
          f"(tolerance {args.tolerance:.0%})")
    rows: list = []
    failures = gate(fresh, baseline, args.tolerance, rows=rows)
    summary_path = os.environ.get("GITHUB_STEP_SUMMARY")
    if summary_path:
        write_step_summary(
            f"{os.path.basename(args.fresh)} vs "
            f"{os.path.basename(args.baseline)} "
            f"(tolerance {args.tolerance:.0%})",
            rows, failures, summary_path)
    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        # name the exact tripped metrics in the last line, so the step's
        # one-line failure annotation says WHAT regressed, not just that
        # something did
        tripped = ", ".join(sorted({f.split(":", 1)[0] for f in failures}))
        print(f"REGRESSION GATE FAILED on: {tripped}", file=sys.stderr)
        return 1
    print("gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
