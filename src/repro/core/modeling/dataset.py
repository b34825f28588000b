"""Training-data generation + cross-validation (paper §3.1.1, §5.3.1).

Exhaustively profiles every (program, dataset, stream-config) cell, caches
the results as JSON (profiling is the expensive one-off "at the factory"
step), and assembles (features ++ config) -> speedup training matrices with
leave-one-out splits over *programs*.

Every cached sample is stamped with the ``device_kind`` that measured it
and keyed ``device_kind/program@scale``: a measurement taken on one kind
of device is never read as one of another.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Optional, Sequence

import numpy as np

from repro.core import REPO_ROOT
from repro.core import features as feat_lib
from repro.core.stream_config import StreamConfig, default_space
from repro.core.streams import StreamedRunner, profile_config_grid
from repro.core.workloads import get_workload, list_workloads


def default_cache_path() -> Path:
    """The profile cache location: ``REPRO_PROFILE_CACHE`` when set
    (resolved per call, so tests and CI can redirect it), else the
    in-repo ``benchmarks/data/profile_cache.json``."""
    env = os.environ.get("REPRO_PROFILE_CACHE")
    return Path(env) if env else (
        REPO_ROOT / "benchmarks" / "data" / "profile_cache.json")


#: import-time snapshot, kept for callers that treat it as a constant;
#: prefer ``default_cache_path()`` (honors a later env override)
DEFAULT_CACHE = default_cache_path()


@dataclasses.dataclass
class Sample:
    """One (program, dataset) cell with its full profiled config grid."""

    program: str
    scale: int
    features: np.ndarray                 # (22,) raw features
    t_single: float                      # single-stream seconds
    times: dict                          # {(p, t): seconds}
    device_kind: str                     # jax ``Device.device_kind``

    def speedup(self, cfg: StreamConfig) -> float:
        return self.t_single / self.times[cfg.as_tuple()]

    @property
    def best_config(self) -> StreamConfig:
        p, t = min(self.times, key=self.times.get)
        return StreamConfig(p, t)

    @property
    def oracle_speedup(self) -> float:
        return self.t_single / min(self.times.values())

    def to_json(self) -> dict:
        return {
            "program": self.program,
            "scale": self.scale,
            "features": self.features.tolist(),
            "t_single": self.t_single,
            "times": [[p, t, v] for (p, t), v in self.times.items()],
            "device_kind": self.device_kind,
        }

    @staticmethod
    def from_json(d: dict) -> "Sample":
        return Sample(
            d["program"], d["scale"], np.asarray(d["features"], np.float64),
            d["t_single"],
            {(p, t): v for p, t, v in d["times"]},
            d["device_kind"],
        )


def cache_key(program: str, scale: int, device_kind: str) -> str:
    return f"{device_kind}/{program}@{scale}"


def cached_sample(cache: dict, program: str, scale: int,
                  device_kind: str) -> Optional[Sample]:
    """The cached measurement of (program, scale) taken on
    ``device_kind``, or None — never one taken on another device."""
    entry = cache.get(cache_key(program, scale, device_kind))
    if entry is None:
        return None
    sample = Sample.from_json(entry)
    if sample.device_kind != device_kind:
        raise ValueError(
            f"profile cache entry {cache_key(program, scale, device_kind)!r} "
            f"was measured on {sample.device_kind!r}")
    return sample


def grid_for(n_rows: int, max_partitions: int = 32,
             max_tasks: int = 64) -> list[StreamConfig]:
    return [c for c in default_space(max_partitions, max_tasks)
            if c.partitions * c.tasks <= n_rows]


def profile_sample(program: str, scale: int, *, reps: int = 2,
                   seed: int = 0) -> Sample:
    wl = get_workload(program)
    rng = np.random.default_rng(seed + scale)
    chunked, shared = wl.make_data(scale, rng)
    runner = StreamedRunner(wl, chunked, shared)
    feats = feat_lib.extract_features(runner, profile_reps=reps)
    grid = grid_for(scale)
    times = profile_config_grid(runner, grid, reps=reps)
    t_single = times[StreamConfig(1, 1)]
    return Sample(program, scale, feats.values, t_single,
                  {c.as_tuple(): v for c, v in times.items()},
                  runner.device.device_kind)


def generate(
    programs: Optional[Sequence[str]] = None,
    *,
    datasets_per_program: int = 4,
    reps: int = 2,
    cache_path: "str | Path | None" = None,
    verbose: bool = True,
) -> list[Sample]:
    """Profile (or load cached) samples for the suite, on the default
    device; only cached samples that device's kind measured are read."""
    import jax

    programs = list(programs or list_workloads())
    cache_path = Path(cache_path) if cache_path else default_cache_path()
    cache = _load_cache(cache_path)
    device_kind = jax.devices()[0].device_kind
    samples: list[Sample] = []
    dirty = False
    for prog in programs:
        wl = get_workload(prog)
        scales = _pick_scales(wl.datasets, datasets_per_program)
        for scale in scales:
            cached = cached_sample(cache, prog, scale, device_kind)
            if cached is not None:
                samples.append(cached)
                continue
            key = cache_key(prog, scale, device_kind)
            t0 = time.perf_counter()
            s = profile_sample(prog, scale, reps=reps)
            cache[key] = s.to_json()
            dirty = True
            samples.append(s)
            if verbose:
                # progress goes to stderr: callers (serve --adaptive,
                # benchmarks) reserve stdout for JSON/CSV payloads
                print(f"profiled {key:28s} oracle={s.oracle_speedup:5.2f}x "
                      f"({time.perf_counter()-t0:5.1f}s)",
                      file=sys.stderr, flush=True)
        if dirty:
            _save_cache(cache_path, cache)  # checkpoint per program
            dirty = False
    return samples


def _pick_scales(scales: tuple, k: int) -> list[int]:
    if k >= len(scales):
        return list(scales)
    idx = np.linspace(0, len(scales) - 1, k).round().astype(int)
    return [scales[i] for i in np.unique(idx)]


def _load_cache(path: "str | Path") -> dict:
    path = Path(path)
    if path.exists():
        with open(path) as f:
            return json.load(f)
    return {}


def _save_cache(path: "str | Path", cache: dict) -> None:
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(path.suffix + ".tmp")
    with open(tmp, "w") as f:
        json.dump(cache, f)
    os.replace(tmp, path)


# ---------------------------------------------------------------------------
# Matrices + cross-validation
# ---------------------------------------------------------------------------


def training_matrix(samples: Sequence[Sample]):
    """Rows = (program features ++ config encoding); target = speedup."""
    X, y = [], []
    for s in samples:
        for (p, t), sec in s.times.items():
            X.append(np.concatenate(
                [s.features, feat_lib.config_features(p, t)]))
            y.append(s.t_single / sec)
    return np.stack(X), np.asarray(y)


def loo_split(samples: Sequence[Sample], test_program: str):
    """Leave-one-out over programs (§5.3.1).  convsepr*/fftx* siblings are
    excluded together, as the paper does for convolutionFFT2d/Separable."""
    fam = _family(test_program)
    train = [s for s in samples if _family(s.program) != fam]
    test = [s for s in samples if s.program == test_program]
    return train, test


def _family(name: str) -> str:
    for prefix in ("convsepr", "fftx"):
        if name.startswith(prefix):
            return prefix
    return name
