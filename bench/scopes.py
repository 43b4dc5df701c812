"""Where a traced step goes: device idle inside each engine phase, and
device time inside each named scope of the fused paged decode program.

    python3 bench/scopes.py <trace-dir> [--config yi-34b-s6]

reads a profile kept by `bench/run.py --trace 1 --trace-dir <trace-dir>`
and prints both tables; the per-layer readers `engine_idle_ms`,
`decode_device_ms` and `kv_pool_pass_ms` share its functions.  Run it on
the machine that recorded the profile: the scope table compiles the
decode program for the device it finds.

Engine phases are the `engine.<component>` annotations that an enabled
`repro.serving.Tracer` puts on the profiler's host line.  Scopes are the
`jax.named_scope`s of the decode program (root `decode_step`, parts
`SCOPES`).  A TPU op event carries its HLO instruction (name, result type,
operands) but not the instruction's `op_name`, so the scope of each op is
read from the optimized HLO of the program itself, which the engine's own
jitted step gives again for the run's configuration, and matched to the
trace's ops by instruction name and result type.  Two kinds of op carry
no part scope: the layer loop's own slices of the stacked weights (under
the root alone: a scope around the loop would name them too) and the ops
XLA makes (no `op_name`).  The layer loop carries the stacked K/V page
pools whole and writes each layer's new token into them in place (the
two page scatters in `kv_pool`), so no other op's result is a whole pool.
A pass over a pool that a change brings back (a copy, a slice, a
write-back, with or without a scope) is found by the pool's shape,
stacked or one layer's as the configuration's family gives them
(`bench/families`), outside the `paged_attention` scope.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

ROOT_DIR = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT_DIR), str(ROOT_DIR / "src")]

from bench import families, trace_reduce  # noqa: E402

PHASE_PREFIX = "engine."
ROOT = "decode_step"
SCOPES = ("embed", "attn_proj", "kv_pool", "paged_attention", "mlp",
          "lm_head", "sample")
UNNAMED = "(no op_name)"     # an XLA-made op: no scope of the program's

Interval = trace_reduce.Interval


# ------------------------------------------------------------ engine phases
def phases(trace, lo: float, hi: float) -> List[trace_reduce.Event]:
    """The `engine.*` host annotations that overlap [lo, hi)."""
    return [ev for ev in trace.host if ev.name.startswith(PHASE_PREFIX)
            and ev.end > lo and ev.start < hi]


def _overlap_ns(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sets of disjoint, sorted
    intervals."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_by_phase(trace, lo: float, hi: float
                  ) -> Optional[Dict[str, float]]:
    """{component: ns of device idle inside its annotations} in [lo, hi),
    with the idle outside every engine phase under "(outside)"; None when
    the trace holds no engine annotation."""
    evs = phases(trace, lo, hi)
    if not evs:
        return None
    idle = trace_reduce.gaps(trace, lo, hi)
    out: Dict[str, float] = {}
    for ev in evs:
        name = ev.name[len(PHASE_PREFIX):]
        span = trace_reduce.clip([(ev.start, ev.end)], lo, hi)
        out[name] = out.get(name, 0.0) + _overlap_ns(idle, span)
    inside = engine_idle_ns(trace, lo, hi)
    out["(outside)"] = sum(e - s for s, e in idle) - inside
    return out


def engine_idle_ns(trace, lo: float, hi: float) -> Optional[float]:
    """Nanoseconds of [lo, hi) in which no device op ran and the host was
    inside an engine phase; None when the trace holds no engine
    annotation."""
    evs = phases(trace, lo, hi)
    if not evs:
        return None
    inside = trace_reduce.union(
        trace_reduce.clip([(ev.start, ev.end) for ev in evs], lo, hi))
    return _overlap_ns(trace_reduce.gaps(trace, lo, hi), inside)


def decode_windows(trace, lo: float, hi: float) -> List[Interval]:
    """Each `engine.decode` annotation's start to the end of the
    `engine.sample` that follows it: the decode program is dispatched
    after the first and has finished before the second returns."""
    evs = sorted(phases(trace, lo, hi), key=lambda ev: ev.start)
    out = []
    for i, ev in enumerate(evs):
        if ev.name != PHASE_PREFIX + "decode":
            continue
        end = next((s.end for s in evs[i + 1:]
                    if s.name == PHASE_PREFIX + "sample"), ev.end)
        out.append((ev.start, end))
    return trace_reduce.clip(trace_reduce.union(out), lo, hi)


# ------------------------------------------------------- the decode program
@dataclasses.dataclass
class Op:
    """One instruction of a program's optimized HLO that runs as an op of
    its own (in the entry computation or a control-flow body)."""
    head: str               # "%name = type opcode(": a TPU op event's start
    dims: Tuple[int, ...]   # result dims (() for a tuple)
    scope: str              # innermost of SCOPES, ROOT, or UNNAMED


_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\()")
_CALLS = re.compile(r"\b(?:body|condition|to_apply|true_computation|"
                    r"false_computation)=%([\w.\-]+)|"
                    r"branch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_DIMS = re.compile(r"^\w+\[([\d,]*)\]")


def scope_of(op_name: str) -> str:
    """The innermost of SCOPES under ROOT on an op_name path; ROOT when
    the op is under the root alone (the layer loop's own slices and
    write-backs); UNNAMED when the op has no op_name or none under ROOT."""
    parts = op_name.split("/")
    if ROOT not in parts:
        return UNNAMED
    inner = [p for p in parts[parts.index(ROOT):] if p in SCOPES]
    return inner[-1] if inner else ROOT


def parse_hlo(text: str) -> Dict[str, Op]:
    """{instruction name: Op} of the computations that run op by op: the
    entry and, from it, the computations that loops, conditionals and
    calls run (not fusions, reducers or sort comparators, whose
    instructions run inside another op)."""
    comps: Dict[str, List[str]] = {}
    entry, cur = None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            comps[cur] = []
            if line.startswith("ENTRY"):
                entry = cur
        elif cur is not None and line.strip() and line.strip() != "}":
            comps[cur].append(line)
    if entry is None:
        return {}
    ops: Dict[str, Op] = {}
    todo, seen = [entry], set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            m = _INSTR.match(line)
            if m is None:
                continue
            head, iname, rtype, opcode = m.groups()
            if opcode in ("while", "conditional", "call"):
                for a, b in _CALLS.findall(line):
                    todo.extend([a] if a else
                                [c.strip().lstrip("%") for c in b.split(",")])
            md = _OP_NAME.search(line)
            dm = _DIMS.match(rtype)
            dims = tuple(int(d) for d in dm.group(1).split(",") if d) \
                if dm else ()
            ops[iname] = Op(head, dims, scope_of(md.group(1) if md else ""))
    return ops


def decode_hlo(conf: dict) -> str:
    """The optimized HLO text of the fused paged decode program that the
    engine runs for configuration `conf`.  The engine's jitted step is
    lowered again with the arguments' shapes; in the process that ran the
    engine this finds the program already compiled."""
    import jax
    import jax.numpy as jnp

    from bench import harness
    from repro.kernels import interpret_mode
    from repro.launch.steps import cached_fused_paged_serve_step
    from repro.nn.model import init_serving_params
    from repro.serving.paging import init_page_pool

    # the engine's ModelConfig carries the configuration's file name
    name = next((p.stem for p in sorted((harness.HERE / "configs")
                                        .glob("*.json"))
                 if harness.load_config(p.stem) == conf), "decode")
    cfg = families.of(conf).model_config(name, conf["config"])
    e = conf["engine"]
    backend = e["kernel_backend"]
    interp = interpret_mode() if backend == "pallas" else False
    fn = cached_fused_paged_serve_step(cfg, backend, interp)
    params = jax.eval_shape(
        lambda: init_serving_params(jax.random.PRNGKey(0), cfg))
    pools = jax.eval_shape(
        lambda: init_page_pool(cfg, e["kv_pages"] + 1, e["page_size"]))
    rows = e["decode_rows"]

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (rows,), dtype)

    return fn.lower(params, arg(jnp.int32), pools, arg(jnp.int32),
                    arg(jnp.int32, rows, e["kv_pages"]), arg(jnp.float32),
                    arg(jnp.int32), arg(jnp.uint32, rows, 2),
                    arg(jnp.int32)).compile().as_text()


def pool_dims(conf: dict) -> Tuple[Tuple[int, ...], ...]:
    """Dims of the decode program's K/V page pools, stacked and one
    layer's, with the page-0 scratch page: the configuration's family's
    `pool_dims` for `kv_pages` + 1 pages."""
    e = conf["engine"]
    return families.shape(conf).pool_dims(e["kv_pages"] + 1, e["page_size"])


class Program:
    """The decode program's ops, matched to a trace's device ops."""

    def __init__(self, ops: Dict[str, Op], pools: Sequence[tuple] = ()):
        self.ops = ops
        self.pools = set(pools)

    @property
    def scoped(self) -> bool:
        """Whether the program carries the decode scopes at all (a program
        built before them does not)."""
        return any(op.scope != UNNAMED for op in self.ops.values())

    def op(self, ev) -> Optional[Op]:
        """The program's op that trace event `ev` ran, or None."""
        op = self.ops.get(ev.name)
        if op is None or not ev.text.startswith(op.head):
            return None
        return op

    def is_pool_pass(self, op: Op, ev) -> bool:
        """An op whose result is a whole K/V pool (stacked, or one
        layer's), outside the paged-attention kernel: it reads or writes
        the pool.  Loops are left out: their bodies' ops are events of
        their own."""
        if ev.name.split(".")[0] in trace_reduce.CONTAINERS:
            return False
        return op.dims in self.pools and op.scope != "paged_attention"


def decode_ops(trace, program: Program, lo: float, hi: float):
    """[(event, op)] of the decode program's ops inside the decode windows
    of [lo, hi)."""
    wins = decode_windows(trace, lo, hi)
    starts = [s for s, _ in wins]
    out = []
    for ev in trace.device_ops:
        # the last window to start before the op ends (windows are
        # disjoint and sorted) is the only one it can overlap
        i = bisect.bisect_left(starts, ev.end) - 1
        if i < 0 or wins[i][1] <= ev.start:
            continue
        op = program.op(ev)
        if op is not None:
            out.append((ev, op))
    return out


def _ns(ev, lo: float, hi: float) -> float:
    """An event's nanoseconds inside [lo, hi)."""
    return max(0.0, min(ev.end, hi) - max(ev.start, lo))


def decode_device_ns(trace, program: Program, lo: float, hi: float
                     ) -> Optional[float]:
    """Nanoseconds in which some op of the decode program ran (the union
    of their intervals) in [lo, hi); None without engine annotations or
    a scoped program."""
    if not program.scoped or not decode_windows(trace, lo, hi):
        return None
    ivs = [(ev.start, ev.end) for ev, _ in decode_ops(trace, program, lo, hi)]
    return sum(e - s for s, e in trace_reduce.union(
        trace_reduce.clip(ivs, lo, hi)))


def pool_pass_ns(trace, program: Program, lo: float, hi: float
                 ) -> Optional[float]:
    """Summed device time of the decode program's pool passes in [lo, hi);
    None without engine annotations or a scoped program."""
    if not program.scoped or not decode_windows(trace, lo, hi):
        return None
    return sum(_ns(ev, lo, hi)
               for ev, op in decode_ops(trace, program, lo, hi)
               if program.is_pool_pass(op, ev))


def ns_by_scope(trace, program: Program, lo: float, hi: float
                ) -> Dict[str, float]:
    """{scope: summed device time} of the decode program's ops in [lo, hi),
    loops left out (their bodies' ops count), every pool pass counted
    under kv_pool."""
    out: Dict[str, float] = {}
    for ev, op in decode_ops(trace, program, lo, hi):
        if ev.name.split(".")[0] in trace_reduce.CONTAINERS:
            continue
        key = "kv_pool" if program.is_pool_pass(op, ev) else op.scope
        out[key] = out.get(key, 0.0) + _ns(ev, lo, hi)
    return out


def run_program(run) -> Optional[Program]:
    """The decode program of a traced run, or None where the run has no
    trace or no known chip (a CPU run reports no device number)."""
    tw = run.trace
    if tw is None or run.peaks is None:
        return None
    return Program(parse_hlo(decode_hlo(run.conf)), pool_dims(run.conf))


def decoded_steps(run) -> int:
    return sum(1 for s in run.trace.steps if s.decode_positions)


# ---------------------------------------------------------------- the tables
def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("trace_dir")
    p.add_argument("--config", default="",
                   help="the cell's configuration (bench/configs/<name>."
                        "json); without it only the phase table is printed")
    args = p.parse_args(argv)
    tr = trace_reduce.load(trace_reduce.find_xplane(args.trace_dir))
    span = trace_reduce.annotation_span(tr, "bench.step")
    if span is None:
        print("the profile holds no bench.step annotation", file=sys.stderr)
        return 1
    lo, hi = span
    n_steps = sum(1 for ev in tr.host if ev.name == "bench.step")
    n_dec = len(decode_windows(tr, lo, hi))
    idle = idle_by_phase(tr, lo, hi)
    idle_ns = sum(e - s for s, e in trace_reduce.gaps(tr, lo, hi))
    print(f"window {(hi - lo) * 1e-6:.3f} ms, {n_steps} steps, {n_dec} "
          f"decoded; device idle {idle_ns * 1e-6 / n_steps:.3f} ms/step")
    if idle is None:
        print("no engine.* annotation: the engine ran without a Tracer")
        return 0
    print(f"{'engine phase':<16}{'idle ms/step':>14}")
    for name, ns in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"{name:<16}{ns * 1e-6 / n_steps:>14.3f}")
    if not args.config:
        return 0
    from bench import harness

    conf = harness.load_config(args.config)
    prog = Program(parse_hlo(decode_hlo(conf)), pool_dims(conf))
    if not prog.scoped or not n_dec:
        print("the decode program carries no scopes, or no step decoded")
        return 0
    by = ns_by_scope(tr, prog, lo, hi)
    dev = decode_device_ns(tr, prog, lo, hi)
    print(f"decode program device time {dev * 1e-6 / n_dec:.3f} ms per "
          f"decoded step; pool passes "
          f"{pool_pass_ns(tr, prog, lo, hi) * 1e-6 / n_dec:.3f} ms")
    print(f"{'scope':<18}{'ms/decoded step':>16}")
    for name, ns in sorted(by.items(), key=lambda kv: -kv[1]):
        print(f"{name:<18}{ns * 1e-6 / n_dec:>16.3f}")
    tot: Dict[Tuple[str, str], float] = {}
    for ev, op in decode_ops(tr, prog, lo, hi):
        if prog.is_pool_pass(op, ev):
            key = (ev.name, op.scope)
            tot[key] = tot.get(key, 0.0) + _ns(ev, lo, hi)
    print(f"{'pool pass':<40}{'scope':>16}{'ms/decoded step':>16}")
    for (name, scope), ns in sorted(tot.items(), key=lambda kv: -kv[1]):
        print(f"{name:<40}{scope:>16}{ns * 1e-6 / n_dec:>16.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
