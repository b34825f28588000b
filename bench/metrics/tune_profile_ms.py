"""Profiling time of one cold tune, in ms: the ``tune.profile`` spans
(the transfer, kernel and single-stream measurements) inside
``tune.cold`` and ``tune.cold.batch``, summed over the window, over the
cold buckets as ``tune_ms_per_cold`` counts them."""
COLD = ("tune.cold", "tune.cold.batch")


def read(run):
    total, n, seen = 0.0, 0, False
    for s in run.spans:
        if s.name == "tune.cold":
            n += 1
        elif s.name == "tune.cold.batch":
            n += int(s.attrs["buckets"])
        elif s.name == "tune.profile" and s.parent in COLD:
            total, seen = total + s.duration_s, True
    return 1e3 * total / n if seen and n else None
