"""Share of the roofline reached by the workload kernels in the profiled
sub-window, in %: the least time the chip could take for every kernel
call of the requests served inside it (the larger of FLOPs over peak
FLOP/s and HBM bytes over peak bandwidth, from ``counts/``) over the
device time of the kernel programs the trace shows there.  The
sub-window is a whole number of the driver's calls, so nothing else ran
on the chip in it.  Nothing to read when a program has no count file."""
from check import call_rows


def read(run):
    dev, peaks = run.device, run.peaks
    if dev is None or peaks is None or dev["kernel_s"] <= 0:
        return None
    least = 0.0
    for d in run.done:
        if not d.traced:
            continue
        mod = run.counts(d.program)
        if mod is None:
            return None
        for lo, hi in call_rows(d.rows, *d.split):
            flops, nbytes = mod.counts(hi - lo)
            least += max(flops / peaks["flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / dev["kernel_s"] if least else None
