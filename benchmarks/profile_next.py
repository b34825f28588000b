"""Incremental profile-corpus growth: profile the next few not-yet-
covered programs of the 39-program suite into the profile cache.

    PYTHONPATH=src python -m benchmarks.profile_next --count 3
    PYTHONPATH=src python -m benchmarks.profile_next --list-covered

The trained model's frac-of-oracle is corpus-bound (ROADMAP: 0.75 on
the 6-program seed corpus, target 0.93 on a broad one), but profiling
the full suite in one sitting is hours of grid sweeps.  This tool makes
growth *incremental*: each invocation picks the first ``--count``
programs (suite order, so runs are deterministic and disjoint) that
have no cached cell yet, profiles ``--datasets`` scales each into the
cache at ``REPRO_PROFILE_CACHE`` (or the committed default), and prints
a JSON report.  The nightly CI job runs this against an actions-cached
copy — three programs per night, zero per-PR cost — and re-evaluates
the model on whatever the corpus has grown to (``--list-covered``
feeds the grown program list to ``benchmarks.run --model-eval``).

Already-covered programs are never re-profiled here; a committed-seed
refresh is a deliberate act (delete cells / change the corpus hash),
not a nightly side effect.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core.modeling import dataset as ds  # noqa: E402
from repro.core.workloads import list_workloads  # noqa: E402


def _profiled_here(cache_path=None) -> set[str]:
    """Programs with at least one cell in the cache measured on this
    process's kind of device."""
    import jax

    kind = jax.devices()[0].device_kind
    cache = ds._load_cache(cache_path or ds.default_cache_path())
    return {e["program"] for e in cache.values() if e["device_kind"] == kind}


def covered_programs(cache_path=None) -> list[str]:
    """Covered programs (see :func:`_profiled_here`), in suite order."""
    have = _profiled_here(cache_path)
    return [p for p in list_workloads() if p in have]


def next_uncovered(count: int, cache_path=None) -> list[str]:
    have = _profiled_here(cache_path)
    return [p for p in list_workloads() if p not in have][:count]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--count", type=int, default=3,
                    help="programs to profile this run (suite order, "
                         "first uncovered)")
    ap.add_argument("--datasets", type=int, default=2,
                    help="dataset scales per program (matches "
                         "--model-eval's --eval-datasets default)")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--cache", default=None,
                    help="profile cache JSON (default: "
                         "REPRO_PROFILE_CACHE or the committed seed)")
    ap.add_argument("--list-covered", action="store_true",
                    help="print the covered program list (comma-"
                         "separated) and exit — the --model-eval input")
    args = ap.parse_args()

    cache_path = args.cache or str(ds.default_cache_path())
    if args.list_covered:
        print(",".join(covered_programs(cache_path)))
        return 0

    todo = next_uncovered(args.count, cache_path)
    report = {
        "cache": cache_path,
        "suite_size": len(list_workloads()),
        "covered_before": len(covered_programs(cache_path)),
        "profiled": todo,
    }
    if not todo:
        remaining = len(next_uncovered(len(list_workloads()), cache_path))
        report["note"] = ("corpus complete: every program has cached cells"
                          if remaining == 0
                          else f"nothing profiled ({remaining} uncovered)")
        print(json.dumps(report, indent=1))
        return 0
    t0 = time.perf_counter()
    # generate() profiles only missing cells and checkpoints the cache
    # atomically per program, so a nightly-job timeout loses at most the
    # in-flight program, never the cache file
    ds.generate(todo, datasets_per_program=args.datasets, reps=args.reps,
                cache_path=cache_path, verbose=True)
    report["covered_after"] = len(covered_programs(cache_path))
    report["wall_s"] = time.perf_counter() - t0
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
