"""Executor throughput: input plus output bytes of the window's requests
over the summed wall of the engine's ``dispatch`` spans (H2D, kernels,
D2H of one request each)."""


def read(run):
    busy = sum(s.duration_s for s in run.spans if s.name == "dispatch")
    moved = sum(d.in_bytes + d.out_bytes for d in run.done if d.ok)
    return moved / busy / 1e9 if busy > 0 and moved else None
