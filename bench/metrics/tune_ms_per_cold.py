"""Mean wall of one cold tune, in ms: ``tune.cold`` spans count one
bucket each, a ``tune.cold.batch`` span counts its ``buckets``."""


def read(run):
    total, n = 0.0, 0
    for s in run.spans:
        if s.name == "tune.cold":
            total, n = total + s.duration_s, n + 1
        elif s.name == "tune.cold.batch":
            total, n = total + s.duration_s, n + int(s.attrs["buckets"])
    return 1e3 * total / n if n else None
