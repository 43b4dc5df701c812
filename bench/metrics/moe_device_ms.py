"""moe_device_ms: device milliseconds per traced step that decoded of the
decode program's operations in its `mlp` scope (`bench/scopes.py`): each
layer's MLP, which in an expert model is the router, the held experts and
the shared experts (scopes `moe_route`, `moe_experts`, `moe_shared` inside
`mlp`), with a leading dense layer's MLP.  A program without the decode
scopes reads nothing."""
from bench import scopes


def read(run):
    prog = scopes.run_program(run)
    n = scopes.decoded_steps(run) if prog is not None else 0
    if not n or not prog.scoped:
        return None
    tw = run.trace
    if not scopes.decode_windows(tw.trace, tw.lo, tw.hi):
        return None
    ns = scopes.ns_by_scope(tw.trace, prog, tw.lo, tw.hi).get("mlp")
    return None if ns is None else ns * 1e-6 / n
