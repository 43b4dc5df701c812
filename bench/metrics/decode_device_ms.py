"""decode_device_ms: device milliseconds of the fused paged decode
program per traced step that decoded: the union of the intervals of its
operations (found by the program's own HLO, `bench/scopes.py`) inside
each step's `engine.decode` and `engine.sample` phases.  What
`decode_step_ms` reads beyond it is the host's share of those phases."""
from bench import scopes


def read(run):
    prog = scopes.run_program(run)
    n = scopes.decoded_steps(run) if prog is not None else 0
    if not n:
        return None
    tw = run.trace
    ns = scopes.decode_device_ns(tw.trace, prog, tw.lo, tw.hi)
    return None if ns is None else ns * 1e-6 / n
