"""How ``correct`` is decided: each checked request's outputs against the
plain reference on the same inputs.

The executor returns one output per kernel call, task-major and
partition-minor.  How they relate to the unsplit result is the
program's ``combine`` in the configuration:

  ``concat``  rows are independent: the outputs, concatenated, are the
              unsplit result;
  ``sum``     each call yields a partial sum: the outputs add up to it;
  ``local``   each call's result depends on its own rows only (chunk
              statistics): each output is compared with the reference
              on that call's rows, cut as the split the request ran
              under cuts them.

The number compared for a request is the largest absolute difference
over a scale (the absolute difference where the scale is 0): the
largest absolute reference value, and for a ``sum`` program the largest
absolute value of the reference run on the magnitudes of its float
inputs.  A ``sum`` program adds terms over all of a request's rows, so
its answer can cancel to near zero while its rounding grows with the
terms it adds: over its own answer the gap swings by orders of
magnitude from request to request, and the widest over a run follows
the smallest answer drawn.  Its kernels add products of their inputs,
so the reference on magnitudes is the sum of the terms' magnitudes.  A
missing output, a wrong shape or a value that is not finite reads
infinity.  A cell's number for a program
is the largest over its checked requests, held against the program's
limit in the configuration.
"""
from __future__ import annotations

import math

import numpy as np

from precision import REFERENCE


def split_bounds(lo: int, hi: int, n: int) -> list[tuple[int, int]]:
    """``np.array_split`` of rows [lo, hi) into n pieces, as bounds."""
    base, rem = divmod(hi - lo, n)
    out, start = [], lo
    for i in range(n):
        size = base + (1 if i < rem else 0)
        out.append((start, start + size))
        start += size
    return out


def call_rows(rows: int, partitions: int, tasks: int) -> list[tuple[int, int]]:
    """Row ranges of every kernel call of a (partitions, tasks) split:
    rows into tasks, each task into partitions."""
    return [b for t_lo, t_hi in split_bounds(0, rows, tasks)
            for b in split_bounds(t_lo, t_hi, partitions)]


def _as(P, d: dict) -> dict:
    return {k: P.arr(v) for k, v in d.items()}


def magnitudes(d: dict) -> dict:
    """Float arrays in absolute value; indices and counts as they are."""
    return {k: np.abs(v) if v.dtype.kind == "f" else v for k, v in d.items()}


def expected(ref, P, combine: str, chunked: dict, shared: dict,
             split: tuple[int, int]) -> list:
    """The reference's answer in the shape the comparison needs: one
    array for ``concat``/``sum``, one per kernel call for ``local``."""
    s = _as(P, shared)
    if combine != "local":
        return [P.out(ref.kernel(P, _as(P, chunked), s))]
    rows = next(iter(chunked.values())).shape[0]
    return [P.out(ref.kernel(P, _as(P, {k: a[lo:hi]
                                        for k, a in chunked.items()}), s))
            for lo, hi in call_rows(rows, *split)]


def combined(outs: list, combine: str) -> list:
    """The program's outputs in the same shape as :func:`expected`."""
    arrs = [np.asarray(o, np.float64) for o in outs]
    if combine == "concat":
        return [np.concatenate(arrs, axis=0)] if arrs else []
    if combine == "sum":
        return [np.sum(arrs, axis=0)] if arrs else []
    if combine == "local":
        return arrs
    raise ValueError(f"unknown combine {combine!r}")


def gap(got: list, want: list, scales: list | None = None) -> float:
    """The number compared for one request (see the module docstring):
    ``scales`` in the shape of ``want``, ``want`` itself when None."""
    if len(got) != len(want) or not want:
        return math.inf
    worst, scale = 0.0, 0.0
    for g, w, m in zip(got, want, want if scales is None else scales):
        if g.shape != w.shape or not np.all(np.isfinite(g)):
            return math.inf
        if g.size:
            worst = max(worst, float(np.max(np.abs(g - w))))
            scale = max(scale, float(np.max(np.abs(m))))
    return worst / scale if scale > 0 else worst


def request_gap(ref, combine: str, chunked: dict, shared: dict,
                split: tuple[int, int], outs: list) -> float:
    want = expected(ref, REFERENCE, combine, chunked, shared, split)
    scales = None
    if combine == "sum":
        scales = expected(ref, REFERENCE, combine, magnitudes(chunked),
                          magnitudes(shared), split)
    return gap(combined(outs, combine), want, scales)


def verdict(gaps: dict, limits: dict) -> tuple[bool, dict]:
    """``gaps``: program -> its largest gap.  Returns whether every
    program is within its limit, and the numbers beside their limits."""
    checks, ok = {}, bool(gaps)
    for prog in sorted(gaps):
        value, limit = gaps[prog], limits.get(prog)
        good = limit is not None and value <= limit
        ok = ok and good
        checks[prog] = {"value": value if math.isfinite(value) else "inf",
                        "limit": limit}
    return ok, checks
