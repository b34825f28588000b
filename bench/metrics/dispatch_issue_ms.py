"""Host time a request spends issuing its work, in ms: the wall of the
executor's ``dispatch.issue`` spans (host slicing, H2D and kernel
enqueue) less the ``dispatch.wait`` spans nested in them (the backend's
window waits), summed over the window, over the number of ``dispatch``
spans."""


def read(run):
    issue = [s.duration_s for s in run.spans if s.name == "dispatch.issue"]
    n = sum(1 for s in run.spans if s.name == "dispatch")
    if not issue or not n:
        return None
    nested = sum(s.duration_s for s in run.spans
                 if s.name == "dispatch.wait" and s.parent == "dispatch.issue")
    return 1e3 * (sum(issue) - nested) / n
