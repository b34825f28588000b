#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

In one process, for each seed: one whole run of the cell (set-up, a
window of ``--seconds``, the check), printing each program's number
compared, the sound run's reading; then, for the first
``--control-seeds`` seeds, the same checked requests with the control
put in the program's place: the plain reference computed one precision
step below the configuration's (``precision.LowerOnDevice``: bfloat16
elementwise, float8 dot operands) on the default device, at the split
each request ran under.  The last line summarises, per program, the
largest sound reading (the lower reading) and the smallest control
reading (the upper one), and whether each sound run and each control
came out correct by the harness's own comparison: every control has to
read ``false``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH / "lib"))


def control_outputs(P):
    """``outputs_of`` for ``CellRun.gaps``: the reference in precision
    ``P`` in the program's place, one output per kernel call for
    ``local`` programs and one for the whole request otherwise.  Inputs
    are rounded to ``P`` before the jitted kernel and its output is
    returned in ``P``, so that neither rounding can be folded away."""
    import jax
    import numpy as np

    import check
    import datagen

    jitted: dict = {}

    def outputs_of(cr, c, ref):
        prog = c.item.program
        if prog not in jitted:
            jitted[prog] = jax.jit(lambda ch, sh, ref=ref: ref.kernel(
                P, ch, sh))
        fn = jitted[prog]
        ch, sh = cr.buckets[(prog, c.item.rows)]
        view = datagen.request_view(ch, c.item.offset, c.item.rows)
        low = {k: P.arr(v) for k, v in view.items()}
        shared = {k: P.arr(v) for k, v in sh.items()}
        if cr.cell.config["programs"][prog]["combine"] != "local":
            calls = [low]
        else:
            calls = [{k: v[lo:hi] for k, v in low.items()}
                     for lo, hi in check.call_rows(c.item.rows, *c.split)]
        return [P.out(fn(x, shared)) for x in calls]
    return outputs_of


def readings(root: Path, workload: str, seeds: list, seconds: float,
             control_seeds: int, platform) -> dict:
    """Per seed, the sound run's numbers and, for the first
    ``control_seeds``, the control's, each judged by ``check.verdict``
    against the configuration's limits.  Returns, per program, the
    lower and the upper reading, and every run's verdict."""
    import check
    import harness

    rows = []
    for i, seed in enumerate(seeds):
        cr = harness.CellRun(root, workload, seed, seconds, False,
                             t_process=time.perf_counter(),
                             platform=platform)
        cr.setup()
        cr.window()
        line = cr.finish()
        limits = cr.cell.config["limits"]
        row = {"seed": seed, "correct": line["correct"],
               "attempted": line["attempted"],
               "metrics": {k: v["value"] for k, v in line["metrics"].items()},
               "program": {p: float(c["value"])
                           for p, c in line["checks"].items()}}
        if i < control_seeds:
            from precision import LowerOnDevice
            of = control_outputs(LowerOnDevice())
            row["control"] = cr.gaps(lambda c, ref: of(cr, c, ref))
            row["control_correct"], row["control_checks"] = check.verdict(
                row["control"], limits)
        print(json.dumps(row, default=str), flush=True)
        rows.append(row)
        del cr
    progs = sorted({p for r in rows for p in r["program"]})
    programs = {}
    for p in progs:
        lower = max(r["program"].get(p, 0.0) for r in rows)
        ups = [r["control"][p] for r in rows if p in r.get("control", {})]
        programs[p] = {"lower": lower,
                       "upper": min(ups) if ups else None,
                       "ratio": (min(ups) / lower if ups and lower > 0
                                 else math.inf if ups else None)}
    return {"programs": programs,
            "sound_correct": [r["correct"] for r in rows],
            "control_correct": [r["control_correct"] for r in rows
                                if "control" in r]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    import harness

    harness.point_compile_cache(BENCH)
    sys.path.insert(0, str(ROOT / "src"))
    summary = readings(ROOT, args.workload, args.seeds, args.seconds,
                       args.control_seeds, platform="tpu")
    print(json.dumps({"summary": summary}, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
