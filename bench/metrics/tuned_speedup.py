"""How much the tuner's cached picks beat single-stream execution, alone:
in the traced run's set-up, after warm-up, each bucket's request runs
single-stream and under its pick, interleaved, 3 times each (the
executor's ``StreamedRunner.run``, dispatch to read-back); the speedup
is the ratio of the fastest of each, averaged over buckets weighted by
the window's requests."""
REPS = 3


def setup(run):
    from repro.core.stream_config import SINGLE_STREAM, StreamConfig
    from repro.core.streams import StreamedRunner
    from repro.core.workloads import get_workload

    backend = run.cell.config["engine"]["backend"]
    speedups = {}
    for bucket, pick in run.picks.items():
        ch, sh = run.buckets[bucket]
        runner = StreamedRunner(get_workload(bucket[0]), ch, sh,
                                backend=backend)
        cfg = StreamConfig(*pick)
        single = tuned = float("inf")
        for _ in range(REPS):
            single = min(single, runner.run(SINGLE_STREAM, reps=1, warmed=True))
            tuned = min(tuned, runner.run(cfg, reps=1, warmed=True))
        speedups[bucket] = single / tuned
    run.store["tuned_speedup"] = speedups


def read(run):
    speedups = run.store.get("tuned_speedup") or {}
    weights = {}
    for d in run.done:
        if (d.program, d.rows) in speedups:
            weights[(d.program, d.rows)] = weights.get((d.program, d.rows), 0) + 1
    total = sum(weights.values())
    if not total:
        return None
    return sum(w * speedups[b] for b, w in weights.items()) / total
