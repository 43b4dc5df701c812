"""kv_pool_pass_ms: device milliseconds per traced step that decoded of
the decode program's passes over whole K/V page pools outside the
paged-attention kernel: its operations in the `kv_pool` scope (the new
token's page scatter, the layer loop's per-layer slice and write-back of
the stacked pool) and the loop's carry copies that XLA makes, which have
no scope and are found by the pool's shape (`bench/scopes.py`)."""
from bench import scopes


def read(run):
    prog = scopes.run_program(run)
    n = scopes.decoded_steps(run) if prog is not None else 0
    if not n:
        return None
    tw = run.trace
    ns = scopes.pool_pass_ns(tw.trace, prog, tw.lo, tw.hi)
    return None if ns is None else ns * 1e-6 / n
