"""From a JAX profiler trace to device busy time, idle gaps and kernel time.

On a TPU the profiler writes one ``*.xplane.pb`` with a plane per device
(``/device:TPU:0``) whose ``XLA Ops`` line holds every operation the
chip ran and whose ``XLA Modules`` line holds one event per executed
program (a kernel call).  Host-to-device and device-to-host copies are
not on these lines (they are host events), so the device's busy time is
the union of its op intervals.  Host planes carry the harness's anchor
annotation, which ties the trace's clock to the host's
``time.perf_counter``.

``load`` reduces a trace file to plain event lists; ``reduce`` turns
one chip's into the numbers the readers use, and ``combine`` makes one
reading of several chips'.  The committed test trace is
the output of ``load`` on a chip trace, so the reduction is checked
without a chip.
"""
from __future__ import annotations

import glob
import os

ANCHOR = "bench.anchor"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(directory: str) -> str | None:
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def short_op(name: str) -> str:
    """``%add.1 = f32[16384,256]{1,0:T(8,128)} add(...)`` ->
    ``%add.1 = f32[16384,256]``."""
    return name.split("{", 1)[0].split(" (", 1)[0].strip()


def load(path: str) -> dict:
    """Plain event lists of one trace: device ops and modules of every
    device plane as ``[name, start_ns, dur_ns]``, and the anchor's start.
    """
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ops, modules, anchor = [], [], None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [[short_op(e.name), e.start_ns, e.duration_ns]
                            for e in line.events]
                elif line.name == MODULES_LINE:
                    modules += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
        elif anchor is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        anchor = e.start_ns
                        break
    return {"ops": ops, "modules": modules, "anchor_ns": anchor}


def union(intervals: list) -> list:
    """Merged, sorted ``[start, end]`` intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def reduce(events: dict, lo_ns: float, hi_ns: float) -> dict:
    """Busy time, idle gaps, op totals and kernel time inside
    ``[lo_ns, hi_ns]``, all in seconds.  Busy is the union of op
    intervals; a gap is a stretch with no op between ``lo`` and ``hi``;
    kernel time is the summed duration of module executions that start
    inside the window."""
    ops = [[s, s + d] for _, s, d in events["ops"]]
    busy = union(clip(ops, lo_ns, hi_ns))
    busy_ns = sum(e - s for s, e in busy)
    gaps, t = [], lo_ns
    for s, e in busy:
        if s > t:
            gaps.append([t, s])
        t = e
    if hi_ns > t:
        gaps.append([t, hi_ns])
    totals: dict = {}
    for name, s, d in events["ops"]:
        if lo_ns <= s < hi_ns:
            totals[name] = totals.get(name, 0.0) + d
    kernel_ns = sum(d for _, s, d in events["modules"] if lo_ns <= s < hi_ns)
    n_modules = sum(1 for _, s, _ in events["modules"] if lo_ns <= s < hi_ns)
    return {
        "window_s": (hi_ns - lo_ns) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "gaps_ns": gaps,
        "op_totals_s": {k: v * 1e-9 for k, v in totals.items()},
        "kernel_s": kernel_ns * 1e-9,
        "kernel_calls": n_modules,
    }


def combine(reds: list) -> dict:
    """Several chips' reductions of one sub-window as one: busy time,
    kernel time, kernel calls and each op's total summed over the chips,
    and the window the sub-window times the number of chips, so that
    ``1 - busy_s / window_s`` is the chips' mean idle share.  The gaps
    are each chip's in turn, on its own trace's clock: their lengths
    compare across chips, their times do not.  One chip's reduction
    comes back equal."""
    totals: dict = {}
    for red in reds:
        for name, s in red["op_totals_s"].items():
            totals[name] = totals.get(name, 0.0) + s
    return {
        "window_s": sum(red["window_s"] for red in reds),
        "busy_s": sum(red["busy_s"] for red in reds),
        "gaps_ns": [g for red in reds for g in red["gaps_ns"]],
        "op_totals_s": totals,
        "kernel_s": sum(red["kernel_s"] for red in reds),
        "kernel_calls": sum(red["kernel_calls"] for red in reds),
    }


def name_gaps(gaps_ns: list, spans: list, to_ns, top: int = 10) -> list:
    """The ``top`` longest gaps, each named by the host spans open at its
    midpoint (``+``-joined, innermost last; ``idle`` when none)."""
    named = []
    for s, e in sorted(gaps_ns, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        open_ = sorted((sp for sp in spans
                        if to_ns(sp.t_start) <= mid <= to_ns(sp.t_end)),
                       key=lambda sp: (sp.t_start, sp.depth))
        names = []
        for sp in open_:
            if sp.name not in names:
                names.append(sp.name)
        named.append(["+".join(names) or "idle", (e - s) * 1e-9])
    return named
