"""Host time a request spends reading its outputs back, in ms: the
``dispatch.read`` spans (every output leaf to a host array), summed over
the window, over the number of ``dispatch`` spans."""


def read(run):
    reads = [s.duration_s for s in run.spans if s.name == "dispatch.read"]
    n = sum(1 for s in run.spans if s.name == "dispatch")
    return 1e3 * sum(reads) / n if reads and n else None
