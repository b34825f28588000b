#!/usr/bin/env python3
"""On-chip smoke test: the streamed executor and adaptive serving on a TPU.

    python3 chip_smoke.py [--seed 0]    # one chip: device, executor, serving
    python3 chip_smoke.py --fleet4      # four chips: one-chip fleet workers

One chip, three phases, all in this process (a chip belongs to one
process at a time):

  1. device    -- ``jax.devices()[0]`` must be a TPU; nothing carries on
                  on the CPU.
  2. executor  -- vecadd, mvmult, scalarprod and binomial at their
                  largest registered dataset, through every runner
                  backend at 1x1, 2x4 and 4x8 on the TPU; outputs must
                  live on the TPU and, combined per ``wl.combine``, match
                  the unsplit kernel jitted on the CPU within the bound
                  stated per program in ``TOLERANCES``.
  3. serving   -- ``adaptive_serve`` on the learnt model, once through
                  ``AdaptiveScheduler`` (window 1) and once through
                  ``ConcurrentScheduler`` (window 4); the model is
                  bootstrapped from measurements taken on this chip, into
                  a fresh registry and profile cache of the smoke's own.

``--fleet4`` runs only the fleet phase: one trace through a fleet of one
worker and through a fleet of four, each worker pinned by the router to
a chip of its own.  This process touches JAX only after both fleets have
exited.

Any failure exits non-zero without the result line.  The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from repro.core.compile_cache import enable_compile_cache  # noqa: E402

#: the platform every phase must run on
PLATFORM = "tpu"

EXECUTOR_PROGRAMS = ("vecadd", "mvmult", "scalarprod", "binomial")
EXECUTOR_CONFIGS = ((1, 1), (2, 4), (4, 8))

#: program -> (bound on |streamed - reference|, elementwise, from the
#: host inputs and the reference; why that bound)
TOLERANCES = {
    "vecadd": (
        lambda c, s, ref: 2.0 ** -23 * np.abs(ref),
        "one f32 rounding of a + b on either backend"),
    "mvmult": (
        lambda c, s, ref: 2.0 ** -8 * (np.abs(c["A"]) @ np.abs(s["v"])),
        "the TPU may run this f32 matvec on the MXU at the default "
        "bf16-pass precision: <= 2^-8 of sum |A||v| per row"),
    "scalarprod": (
        lambda c, s, ref: 2.0 ** -16 * np.sum(
            np.abs(c["a"].astype(np.float64) * c["b"])),
        "f32 sum of 4M products in another order per backend and split: "
        "far below 2^-16 of sum |a*b|"),
    "binomial": (
        lambda c, s, ref: 2.0 ** -16 * 4.0 * np.max(c["S0"]),
        "pow on the TPU is good to ~2^-16 relative, not to CPU ulps; lattice "
        "prices reach S0*u^48 = 4*S0 and the backward induction averages"),
}

SERVE_WORKLOADS = ("vecadd", "dotprod", "mvmult")
SERVE_REQUESTS = 12
SERVE_TENANTS = 2

FLEET_REQUESTS = 24
FLEET_CHIPS = 4


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_phase():
    import jax

    dev = jax.devices()[0]
    check(dev.platform == PLATFORM,
          f"jax.devices()[0] is {dev.platform!r} ({dev.device_kind}), "
          f"not {PLATFORM!r}")
    log(f"device: {dev.platform} {dev.device_kind} "
        f"x{len(jax.devices())}")
    return dev


def _combine(outs: list, combine: str) -> np.ndarray:
    if combine == "concat":
        return np.concatenate([np.asarray(o) for o in outs], axis=0)
    check(combine == "sum", f"no reference combine for {combine!r}")
    return np.sum([np.asarray(o, np.float64) for o in outs], axis=0)


def executor_phase(dev, seed: int) -> None:
    import jax

    from repro.core.backends import list_backends
    from repro.core.stream_config import StreamConfig
    from repro.core.streams import StreamedRunner
    from repro.core.workloads import get_workload

    cpu = jax.devices("cpu")[0]
    backends = list_backends("runner")
    for name in EXECUTOR_PROGRAMS:
        wl = get_workload(name)
        scale = wl.datasets[-1]
        chunked, shared = wl.make_data(scale, np.random.default_rng(seed))
        ref = np.asarray(jax.jit(wl.kernel)(jax.device_put(chunked, cpu),
                                            jax.device_put(shared, cpu)))
        bound_fn, reason = TOLERANCES[name]
        bound = bound_fn(chunked, shared, ref)
        for backend in backends:
            runner = StreamedRunner(wl, chunked, shared, device=dev,
                                    backend=backend)
            for p, t in EXECUTOR_CONFIGS:
                cell = f"{name}@{scale} {backend} {p}x{t}"
                t0 = time.perf_counter()
                outs = runner.dispatch(StreamConfig(p, t))
                jax.block_until_ready(outs)
                wall = time.perf_counter() - t0
                for leaf in jax.tree.leaves(outs):
                    check(leaf.devices() == {dev},
                          f"{cell}: an output lives on {leaf.devices()}")
                got = _combine(outs, wl.combine)
                check(got.shape == ref.shape,
                      f"{cell}: shape {got.shape} != {ref.shape}")
                err = np.abs(got - ref)
                check(bool(np.all(np.isfinite(got))),
                      f"{cell}: non-finite output")
                check(bool(np.all(err <= bound)),
                      f"{cell}: max |err| {float(np.max(err))!r} exceeds "
                      f"the bound ({reason})")
                log(f"executor {cell}: ok, max |err| {float(np.max(err))!r}"
                    f" (bound {float(np.min(bound))!r}..."
                    f"{float(np.max(bound))!r}), first call {wall:.3f}s")


def serving_phase(dev, seed: int) -> None:
    from repro.launch.serve import adaptive_serve, resolve_serving_model

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        # a registry and profile cache of the smoke's own: nothing from
        # the checkout (a local models/, the committed CPU corpus) leaks in
        os.environ["REPRO_MODEL_DIR"] = os.path.join(tmp, "models")
        os.environ["REPRO_PROFILE_CACHE"] = os.path.join(
            tmp, "profile_cache.json")
        t0 = time.perf_counter()
        _, info = resolve_serving_model("latest")
        log(f"bootstrap_s {time.perf_counter() - t0:.1f}")
        with open(os.environ["REPRO_PROFILE_CACHE"]) as f:
            corpus = json.load(f)
        kinds = {e["device_kind"] for e in corpus.values()}
        check(kinds == {dev.device_kind},
              f"bootstrap corpus measured on {kinds}, not {dev.device_kind}")
        log(f"bootstrap: {info['artifact_id']} from {len(corpus)} samples "
            f"measured on {dev.device_kind}")

        # 3 workloads x 2 tenants alternate, so the first 6 requests are
        # cold and the last 6 repeat a (tenant, workload) bucket
        warm = SERVE_REQUESTS - len(SERVE_WORKLOADS) * SERVE_TENANTS
        for window in (1, 4):
            s = adaptive_serve(
                SERVE_WORKLOADS, n_requests=SERVE_REQUESTS,
                backend="host-pipelined", window=window,
                tenants=SERVE_TENANTS, model="latest", seed=seed,
                verbose=False)
            engine = "AdaptiveScheduler" if window == 1 \
                else "ConcurrentScheduler"
            check(s["model"]["artifact_id"] == info["artifact_id"],
                  f"{engine} served {s['model']}")
            check(s["requests"] == SERVE_REQUESTS,
                  f"{engine}: {s['requests']} of {SERVE_REQUESTS} requests")
            check(s["by_status"] == {"ok": SERVE_REQUESTS},
                  f"{engine}: statuses {s['by_status']}")
            check(s["cache_hits"] == warm,
                  f"{engine}: {s['cache_hits']} tuning-cache hits, "
                  f"expected {warm}")
            log(f"serving {engine} window={window}: "
                f"{s['requests']} served, {s['cache_hits']} warm hits, "
                f"{s['refinements']} refinements, "
                f"{s['throughput_rps']:.1f} req/s, wall {s['wall_s']:.2f}s")


def fleet_phase(seed: int) -> dict:
    """Fleets of 1 and FLEET_CHIPS workers; returns the device line."""
    from repro.launch.serve import fleet_serve

    for n in (1, FLEET_CHIPS):
        s = fleet_serve(SERVE_WORKLOADS, n_requests=FLEET_REQUESTS,
                        worker_procs=n, window=2, backend="host-pipelined",
                        tenants=8, model="heuristic", seed=seed,
                        verbose=False)
        devices = s["devices"]
        log(f"fleet{n} devices: {json.dumps(devices, sort_keys=True)}")
        check(s["requests"] == FLEET_REQUESTS
              and s["by_status"] == {"ok": FLEET_REQUESTS},
              f"fleet{n}: {s['requests']} results, statuses {s['by_status']}")
        check(all(d["platform"] == PLATFORM for d in devices.values()),
              f"fleet{n}: a worker is not on {PLATFORM!r}")
        # each worker holds exactly one chip, and no two hold the same
        chips = [tuple(d["chip_nodes"]) for d in devices.values()]
        check(all(len(c) == 1 for c in chips) and len(set(chips)) == n,
              f"fleet{n}: workers hold chips {chips}")
        run_wall = s["ipc"]["wall_s"]
        log(f"fleet{n}: {FLEET_REQUESTS} served, "
            f"{FLEET_REQUESTS / run_wall:.1f} req/s over the run "
            f"({run_wall:.2f}s), {s['throughput_rps']:.2f} req/s "
            f"with worker start-up ({s['wall_s']:.2f}s)")

    # every worker has exited: the chips are free for this process
    import jax

    devs = jax.devices()
    check(devs[0].platform == PLATFORM and len(devs) == FLEET_CHIPS,
          f"this host has {len(devs)} {devs[0].platform} devices")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated dataset and trace")
    ap.add_argument("--fleet4", action="store_true",
                    help=f"run only the {FLEET_CHIPS}-chip fleet phase")
    args = ap.parse_args(argv)
    enable_compile_cache()
    t0 = time.perf_counter()
    if args.fleet4:
        device = fleet_phase(args.seed)
    else:
        dev = device_phase()
        executor_phase(dev, args.seed)
        serving_phase(dev, args.seed)
        import jax
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
    log(f"smoke wall {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
