"""kv_pool_pass_ms: device milliseconds per traced step that decoded of
the decode program's operations whose result is a whole K/V page pool,
outside the paged-attention kernel, found by the pool's shape
(`bench/scopes.py`): with the pools kept in place through the layer loop,
the new token's page scatters (scope `kv_pool`); any copy, slice or
write-back of a pool, with or without a scope, adds to it."""
from bench import scopes


def read(run):
    prog = scopes.run_program(run)
    n = scopes.decoded_steps(run) if prog is not None else 0
    if not n:
        return None
    tw = run.trace
    ns = scopes.pool_pass_ns(tw.trace, prog, tw.lo, tw.hi)
    return None if ns is None else ns * 1e-6 / n
