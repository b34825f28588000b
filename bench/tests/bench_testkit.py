"""Helpers of the benchmark's tests: the paths, and a tiny cell that runs
on the CPU in a few seconds, added to a copy of the benchmark as files
only."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_CONFIG = {
    "name": "tiny",
    "source": "https://arxiv.org/abs/2003.04294",
    "reduced": ["rows"],
    "precision": "float32",
    "engine": {"backend": "host-pipelined", "window": 4, "workers": 4,
               "model": "heuristic"},
    "programs": {
        "vecadd": {"rows": [64, 96], "combine": "concat",
                   "chunked": {"a": {"shape": [256], "dist": "normal"},
                               "b": {"shape": [256], "dist": "normal"}},
                   "shared": {}},
        "mvmult": {"rows": [128], "combine": "concat",
                   "chunked": {"A": {"shape": [768], "dist": "normal"}},
                   "shared": {"v": {"shape": [768], "dist": "normal"}}},
        "scalarprod": {"rows": [64], "combine": "sum",
                       "chunked": {"a": {"shape": [1024], "dist": "normal"},
                                   "b": {"shape": [1024], "dist": "normal"}},
                       "shared": {}},
        "covariance": {"rows": [256], "combine": "local",
                       "chunked": {"x": {"shape": [128], "dist": "normal"}},
                       "shared": {}},
    },
    # float32 on the CPU reads about 1e-6; bfloat16 about 1e-3 or more.
    # scalarprod, over the magnitudes of its terms: float32 4e-9 to
    # 1.3e-8, the control 1.6e-5 to 3.8e-5 (3 seeds each)
    "limits": {"vecadd": 1e-4, "mvmult": 1e-4, "scalarprod": 1.5e-6,
               "covariance": 1e-4},
}

TINY_TRAFFIC = {
    "driver": "closed_backlog", "requests_per_run": 8, "block": 8,
    "tenants": 2, "isolate_tenants": True, "tuning_cache": "empty",
    "check_every": 2,
}


def tiny_copy(tmp: Path) -> Path:
    """A copy of the benchmark with the tiny cell added as new files and
    new ``BENCHMARK.json`` entries; returns the copy's root."""
    root = Path(tmp) / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".work",
                                                  "__pycache__"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    (root / "bench/traffic/tiny-mix.json").write_text(json.dumps(TINY_TRAFFIC))
    bm["configs"].append({"name": "tiny", "source": TINY_CONFIG["source"],
                          "file": "bench/configs/tiny.json",
                          "reduced": ["rows"], "why": "test"})
    bm["workloads"].append({"name": "tiny.mix", "config": "tiny",
                            "traffic": "tiny-mix", "chips": 1,
                            "why": "test"})
    for m in bm["per_layer"]:
        m["workloads"].append("tiny.mix")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root
