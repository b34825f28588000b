"""Host time a request spends in the executor's warm-up dispatch, in
ms: the ``dispatch.warmup`` spans (a bucket and split's first dispatch
in the engine, once per tuning-cache key), summed over the window, over
the number of ``dispatch`` spans; 0 when the program records dispatch
phases and none warmed up."""


def read(run):
    if not any(s.name == "dispatch.issue" for s in run.spans):
        return None
    n = sum(1 for s in run.spans if s.name == "dispatch")
    warm = sum(s.duration_s for s in run.spans if s.name == "dispatch.warmup")
    return 1e3 * warm / n
