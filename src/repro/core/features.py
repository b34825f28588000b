"""Feature extraction (paper §3.2, Tables 1-2) — TPU/JAX adaptation.

The paper uses static code features + hardware performance counters.  On a
JAX stack the compiled HLO *is* the program, so static features come from
the lowered/compiled kernel (op mix, FLOPs, memory traffic) and dynamic
features from profiling the first iterations of the single-stream version
(paper §3.3: "profiling the program without partitioning for a few loop
iterations").  No hardware counters needed — see DESIGN.md §2.

22 raw features are defined; the model pipeline (perf_model.FeaturePipeline)
applies Z-score standardization, |rho|>0.7 correlation pruning and PCA —
exactly the paper's §3.2.1-§3.2.2 recipe.
"""
from __future__ import annotations

import dataclasses
import math
import re

import numpy as np

from repro.core.streams import StreamedRunner
from repro.core.workloads import Workload
from repro.core.xla_cost import cost_analysis_dict

RAW_FEATURE_NAMES = [
    # --- static: iteration space / transfer structure (paper Table 1) ---
    "loop_nest",            # rank of the widest chunked array
    "loop_count",           # outer iteration count (rows)
    "n_xfer_mem",           # # of host-device transferred buffers
    "dts",                  # total host-device transfer size (bytes)
    "redundant_transfer",   # shared-buffer bytes (re-usable across tasks)
    "max_blocks",           # max #tasks (= loop_count)
    "min_task_unit",        # bytes per iteration row
    "out_bytes",            # device->host result size
    # --- static: compiled-kernel op mix (counter analogues) ---
    "hlo_ops",              # # instructions (paper: # instructions)
    "flops",                # FLOPs of one full pass
    "bytes_accessed",       # memory traffic estimate
    "arith_intensity",      # flops / bytes
    "frac_dot",             # fraction of dot/conv ops
    "frac_elementwise",
    "frac_reduce",
    "n_transcendental",     # exp/log/erf/sin/cos ops (paper: ALU mix)
    "n_gather_scatter",     # irregular access (paper: cache-miss proxy)
    "sequential_inner",     # has inner sequential scan (paper: loop nest)
    # --- dynamic: first-iterations profile ---
    "t_single_us",          # single-stream time (few iterations)
    "t_transfer_us",        # H2D time
    "t_compute_us",         # kernel time
    "comp_comm_ratio",      # log(t_compute / t_transfer) (paper Fig 17)
]

_TRANSCENDENTAL = re.compile(
    r"\b(exponential|log|power|tanh|erf|sine|cosine|rsqrt|sqrt|exp)\b")
_DOT = re.compile(r"\b(dot|dot-general|convolution)\b")
_REDUCE = re.compile(r"\breduce\b")
_GATHER = re.compile(r"\b(gather|scatter|dynamic-slice|dynamic-update-slice)\b")
_ELEMENTWISE = re.compile(
    r"\b(add|subtract|multiply|divide|maximum|minimum|select|compare|and|or|xor)\b")


def _tree_bytes(d: dict) -> int:
    return int(sum(a.nbytes for a in d.values()))


def _tree_count(d: dict) -> int:
    return len(d)


@dataclasses.dataclass
class RawFeatures:
    values: np.ndarray  # (22,)

    def as_dict(self) -> dict:
        return dict(zip(RAW_FEATURE_NAMES, self.values))


def extract_features(runner: StreamedRunner, *, profile: bool = True,
                     profile_reps: int = 2) -> RawFeatures:
    """All 22 raw features: the static ones, as :func:`static_features`
    gives them, then :func:`profiled_features` (zeros, and a ratio of 0,
    without ``profile``)."""
    static = _static_values(runner, runner.lowered_kernel())
    dynamic = (profiled_features(runner, reps=profile_reps) if profile
               else _dynamic_values(0.0, 0.0, 0.0))
    return RawFeatures(np.concatenate((static, dynamic)))


def static_features(runner: StreamedRunner) -> np.ndarray:
    """The 18 static features: the transfer structure, and the compiled
    single-chunk kernel's cost analysis and op mix (a lower, a compile
    or a compile-cache load, ``cost_analysis`` and a scan of the HLO
    text).  Nothing runs on the device."""
    # lowered here and in extract_features, at the same call depth: the
    # HLO text's location tables hold the lowering caller's stack, and
    # ``hlo_ops`` counts their lines
    return _static_values(runner, runner.lowered_kernel())


def _static_values(runner: StreamedRunner, lowered) -> np.ndarray:
    wl, chunked, shared = runner.wl, runner.chunked, runner.shared
    rows = next(iter(chunked.values())).shape[0]
    loop_nest = max(a.ndim for a in chunked.values())
    dts = _tree_bytes(chunked) + _tree_bytes(shared)
    red = _tree_bytes(shared)

    compiled = lowered.compile()
    cost = cost_analysis_dict(compiled)  # {} on backends without analysis
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0)) or float(dts)

    hlo = compiled.as_text()
    op_lines = [ln for ln in hlo.splitlines()
                if "=" in ln and not ln.strip().startswith(("HloModule", "ENTRY", "%", "ROOT %"))]
    n_ops = max(len(op_lines), 1)
    joined = "\n".join(op_lines)
    n_dot = len(_DOT.findall(joined))
    n_red = len(_REDUCE.findall(joined))
    n_elem = len(_ELEMENTWISE.findall(joined))
    n_trans = len(_TRANSCENDENTAL.findall(joined))
    n_gs = len(_GATHER.findall(joined))

    out_shapes = _output_bytes(wl, chunked, shared)
    return np.array([
        loop_nest,
        rows,
        _tree_count(chunked) + _tree_count(shared),
        dts,
        red,
        rows,
        dts / max(rows, 1),
        out_shapes,
        n_ops,
        flops,
        bytes_acc,
        flops / max(bytes_acc, 1.0),
        n_dot / n_ops,
        n_elem / n_ops,
        n_red / n_ops,
        n_trans,
        n_gs,
        1.0 if wl.sequential_inner else 0.0,
    ], dtype=np.float64)


def profiled_features(runner: StreamedRunner, *, reps: int = 2) -> np.ndarray:
    """The 4 dynamic features, from three measurements on the device:
    the H2D transfer, the kernel alone and a single-stream run (the
    last two each warm up first)."""
    t_xfer = runner.measure_transfer(reps=reps)
    t_comp = runner.measure_compute(reps=reps)
    t_single = runner.run_single_stream(reps=reps)
    return _dynamic_values(t_single, t_xfer, t_comp)


def _dynamic_values(t_single: float, t_xfer: float,
                    t_comp: float) -> np.ndarray:
    ratio = math.log(max(t_comp, 1e-9) / max(t_xfer, 1e-9))
    return np.array([t_single * 1e6, t_xfer * 1e6, t_comp * 1e6, ratio],
                    dtype=np.float64)


def _output_bytes(wl: Workload, chunked: dict, shared: dict) -> float:
    import jax

    spec = lambda d: {k: jax.ShapeDtypeStruct(a.shape, a.dtype)
                      for k, a in d.items()}
    out = jax.eval_shape(wl.kernel, spec(chunked), spec(shared))
    return float(sum(np.prod(s.shape) * s.dtype.itemsize
                     for s in jax.tree.leaves(out)))


def config_features(partitions: int, tasks: int) -> np.ndarray:
    """Configuration encoding appended to the program features (§3.1.3)."""
    return np.array([
        math.log2(partitions),
        math.log2(tasks),
        math.log2(tasks / partitions) if tasks >= partitions else -1.0,
    ], dtype=np.float64)


# Candidate grids are immutable per scheduler/tuner, so their encodings —
# and the raw (partitions, tasks) columns the vectorized heuristic model
# scores — are memoized by the grid's value.  Coordinator-thread only:
# decide/tune never runs on pool workers.
_CONFIG_MATRIX_CACHE: dict = {}
_CONFIG_MATRIX_CACHE_MAX = 64


def _config_memo(kind: str, configs, build):
    key = (kind, tuple((c.partitions, c.tasks) for c in configs))
    hit = _CONFIG_MATRIX_CACHE.get(key)
    if hit is None:
        while len(_CONFIG_MATRIX_CACHE) >= _CONFIG_MATRIX_CACHE_MAX:
            _CONFIG_MATRIX_CACHE.pop(next(iter(_CONFIG_MATRIX_CACHE)))
        hit = _CONFIG_MATRIX_CACHE[key] = build()
    return hit


def config_feature_matrix(configs) -> np.ndarray:
    """(C, N_CONFIG_FEATURES) encoding of a candidate grid, memoized."""
    return _config_memo("enc", configs, lambda: np.stack(
        [config_features(c.partitions, c.tasks) for c in configs]))


def config_pt_arrays(configs) -> tuple[np.ndarray, np.ndarray]:
    """The (partitions, tasks) columns of a candidate grid as float
    arrays, memoized — the vectorized overlap heuristic scores the whole
    grid with these instead of a Python loop."""
    return _config_memo("pt", configs, lambda: (
        np.array([c.partitions for c in configs], dtype=np.float64),
        np.array([c.tasks for c in configs], dtype=np.float64)))


N_CONFIG_FEATURES = 3
