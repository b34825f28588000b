"""1 - busy / length of the profiled sub-window, where busy is the union
of the chip's op intervals (profiler trace)."""


def read(run):
    dev = run.device
    if dev is None or dev["window_s"] <= 0:
        return None
    return 1.0 - dev["busy_s"] / dev["window_s"]
