"""Host time a request spends blocked on the device, in ms: every
``dispatch.wait`` span (the backend's window waits and the final
``block_until_ready``), summed over the window, over the number of
``dispatch`` spans."""


def read(run):
    waits = [s.duration_s for s in run.spans if s.name == "dispatch.wait"]
    n = sum(1 for s in run.spans if s.name == "dispatch")
    return 1e3 * sum(waits) / n if waits and n else None
