"""95th percentile of request latency over every request of the window:
from the engine taking the request off its queue (the harness's stamp)
to its retirement (the engine's telemetry stamp, same clock).  A failed
request counts as infinitely late."""
import math

from stats import percentile


def read(run):
    lats = sorted((d.t_retire - d.t_pop) * 1e3 if d.ok else math.inf
                  for d in run.done)
    if not lats:
        return None
    p = percentile(lats, 0.95)
    return p if math.isfinite(p) else None
