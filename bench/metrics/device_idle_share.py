"""device_idle_share: percent of the traced window in which no operation
ran on the device (1 - union of operation intervals / window)."""
from bench import trace_reduce


def read(run):
    tw = run.trace
    # no device number without a known chip's peaks (never from a CPU run)
    if tw is None or run.peaks is None or tw.hi <= tw.lo:
        return None
    busy = trace_reduce.busy_ns(tw.trace, tw.lo, tw.hi)
    return 100.0 * (1.0 - busy / (tw.hi - tw.lo))
