"""engine_idle_ms: milliseconds per traced step in which no operation ran
on the device while the host was inside one of the engine's phases (the
`engine.*` annotations of its `Tracer`): the union of device-op intervals
left idle, clipped to the phases (`bench/scopes.py`).  The rest of the
device's idle time falls in the benchmark's own loop (`bench/harness.py`)."""
from bench import scopes


def read(run):
    tw = run.trace
    # no device number without a known chip's peaks (never from a CPU run)
    if tw is None or run.peaks is None or not tw.steps:
        return None
    ns = scopes.engine_idle_ns(tw.trace, tw.lo, tw.hi)
    return None if ns is None else ns * 1e-6 / len(tw.steps)
