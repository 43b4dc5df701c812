"""The readers of the engine's phases and the decode program's scopes
(`bench/scopes.py`): device idle inside the engine's annotations on a CPU
trace of a traced engine (`bench/testdata/make_engine_trace.py`), the
decode program's device time and pool passes on a synthetic trace, and
nothing read from a trace that has neither."""
import copy
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import scopes
from bench import trace_reduce as tr
from bench.metrics import decode_device_ms, engine_idle_ms, kv_pool_pass_ms

DATA = Path(__file__).resolve().parents[1] / "testdata"
ENGINE_TRACE = DATA / "engine_cpu_trace.xplane.pb"
OLD_TRACE = DATA / "cpu_trace.xplane.pb"


@pytest.fixture(scope="module")
def engine_trace():
    return tr.load(str(ENGINE_TRACE))


def run_of(trace, lo, hi, n_steps, conf=None, decoded=True):
    """A stand-in for `bench.record.Run` with what the readers read."""
    step = SimpleNamespace(decode_positions=[5] if decoded else [])
    return SimpleNamespace(
        trace=SimpleNamespace(trace=trace, lo=lo, hi=hi,
                              steps=[step] * n_steps),
        peaks=object(), conf=conf)


def test_engine_idle_is_idle_inside_the_engine_phases(engine_trace):
    t = engine_trace
    lo, hi = tr.annotation_span(t, "bench.step")
    phases = [(ev.start, ev.end) for ev in t.host
              if ev.name.startswith("engine.")]
    assert len(phases) == 3 * 8     # 8 phases in each of 3 steps
    # by hand: the window's instants with no op, and inside some phase,
    # pair by pair over the two disjoint interval sets
    busy = tr.union(tr.clip([(e.start, e.end) for e in t.device_ops],
                            lo, hi))
    idle, at = [], lo
    for s, e in busy:
        if s > at:
            idle.append((at, s))
        at = max(at, e)
    if hi > at:
        idle.append((at, hi))
    inside = tr.union(tr.clip(phases, lo, hi))
    want = sum(max(0.0, min(e1, e2) - max(s1, s2))
               for s1, e1 in idle for s2, e2 in inside)
    got = scopes.engine_idle_ns(t, lo, hi)
    assert got == pytest.approx(want)
    # the two 20 ms sleeps between the steps are idle outside every phase
    total = sum(e - s for s, e in idle)
    assert 0 < got < total - 2 * 15e6
    by = scopes.idle_by_phase(t, lo, hi)
    assert set(by) == {"schedule", "install", "prefill", "page", "inputs",
                       "decode", "sample", "bookkeep", "(outside)"}
    assert sum(by.values()) == pytest.approx(total)
    assert engine_idle_ms.read(run_of(t, lo, hi, 3)) == pytest.approx(
        want * 1e-6 / 3)


def test_decode_windows_run_from_decode_to_the_end_of_sample(engine_trace):
    t = engine_trace
    lo, hi = tr.annotation_span(t, "bench.step")
    wins = scopes.decode_windows(t, lo, hi)
    dec = [ev for ev in t.host if ev.name == "engine.decode"]
    smp = [ev for ev in t.host if ev.name == "engine.sample"]
    assert wins == [(d.start, s.end) for d, s in zip(dec, smp)]


# A decode program: the entry runs the layer loop `while.9`, whose body
# holds the layer's ops; names, types and op_names as the TPU compiler
# prints them.  Pools here are [2, 9, 8, 32] stacked, [9, 8, 32] a layer.
HLO = """\
HloModule jit_fused_paged_serve_step

%fused_computation.1 (p.1: bf16[9,8,32]) -> bf16[9,8,32] {
  %p.1 = bf16[9,8,32]{2,1,0} parameter(0)
  ROOT %neg.1 = bf16[9,8,32]{2,1,0} negate(%p.1), metadata={op_name="jit(f)/decode_step/kv_pool/scatter"}
}

%body.2 (arg.2: (s32[], bf16[2,9,8,32])) -> (s32[], bf16[2,9,8,32]) {
  %arg.2 = (s32[], bf16[2,9,8,32]{3,2,1,0}) parameter(0)
  %get-tuple-element.3 = bf16[2,9,8,32]{3,2,1,0} get-tuple-element(%arg.2), index=1
  %dynamic-slice_bitcast_fusion.4 = bf16[9,8,32]{2,1,0:T(8,128)(2,1)} fusion(%get-tuple-element.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/decode_step/while/body/squeeze"}
  %fusion.5 = bf16[9,8,32]{2,1,0:T(8,128)(2,1)} fusion(%dynamic-slice_bitcast_fusion.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/decode_step/while/body/closed_call/kv_pool/scatter"}
  %paged_attention.6 = bf16[9,8,32]{2,1,0:T(8,128)(2,1)} custom-call(%fusion.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/decode_step/while/body/closed_call/paged_attention/pallas_call"}
  %fusion.7 = bf16[4,64]{1,0:T(8,128)(2,1)} fusion(%fusion.5), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(f)/decode_step/while/body/closed_call/mlp/dot_general"}
  ROOT %tuple.8 = (s32[], bf16[2,9,8,32]{3,2,1,0}) tuple(%get-tuple-element.3)
}

ENTRY %main.1 (caches: bf16[2,9,8,32]) -> bf16[2,9,8,32] {
  %caches = bf16[2,9,8,32]{3,2,1,0} parameter(0)
  %tuple.9 = (s32[], bf16[2,9,8,32]{3,2,1,0}) tuple(%caches)
  %while.9 = (s32[], bf16[2,9,8,32]{3,2,1,0}) while(%tuple.9), condition=%body.2, body=%body.2, metadata={op_name="jit(f)/decode_step/while"}
  %get-tuple-element.10 = bf16[2,9,8,32]{3,2,1,0} get-tuple-element(%while.9), index=1
  ROOT %copy.11 = bf16[2,9,8,32]{3,2,1,0:T(8,128)(2,1)} copy(%get-tuple-element.10)
}
"""
POOLS = [(2, 9, 8, 32), (9, 8, 32)]


def program():
    return scopes.Program(scopes.parse_hlo(HLO), POOLS)


def test_parse_hlo_reads_op_by_op_computations_and_their_scopes():
    ops = scopes.parse_hlo(HLO)
    assert "neg.1" not in ops          # inside a fusion: not an op event
    got = {n: (op.scope, op.dims) for n, op in ops.items()
           if not n.startswith(("get-tuple", "tuple", "arg", "caches"))}
    assert got == {
        "dynamic-slice_bitcast_fusion.4": ("decode_step", (9, 8, 32)),
        "fusion.5": ("kv_pool", (9, 8, 32)),
        "paged_attention.6": ("paged_attention", (9, 8, 32)),
        "fusion.7": ("mlp", (4, 64)),
        "while.9": ("decode_step", ()),
        "copy.11": (scopes.UNNAMED, (2, 9, 8, 32)),
    }
    assert ops["copy.11"].head == \
        "%copy.11 = bf16[2,9,8,32]{3,2,1,0:T(8,128)(2,1)} copy("
    assert scopes.scope_of("jit(f)/while/body/paged_attention/pallas_call") \
        == scopes.UNNAMED               # not under the root: an old program
    assert not scopes.Program(
        scopes.parse_hlo(HLO.replace("decode_step/", "")), POOLS).scoped


def ev(name, start, end, text=None):
    return tr.Event(name, start, end, text or name)


def op_ev(name, start, end, prog, head=None):
    """A device op event as a TPU trace carries it: the instruction with
    typed operands."""
    head = head or prog.ops[name].head
    return ev(name, start, end, head + "bf16[9,8,32] %x), kind=kLoop")


def synthetic_trace():
    """Two steps (ns): decode dispatched at 100 and 1100, its tokens read
    by 400 and 1400.  Step 1 runs the whole program; step 2 runs the same
    ops again plus one that another program also names `fusion.7` (other
    result type), and an op of the program falls between the steps."""
    p = program()
    host = [ev("bench.step", 0, 500), ev("engine.decode", 100, 150),
            ev("engine.sample", 150, 400),
            ev("bench.step", 1000, 1500), ev("engine.decode", 1100, 1150),
            ev("engine.sample", 1150, 1400)]
    dev = []
    for base in (0, 1000):
        dev += [op_ev("while.9", base + 160, base + 300, p),
                op_ev("dynamic-slice_bitcast_fusion.4", base + 160,
                      base + 180, p),
                op_ev("fusion.5", base + 180, base + 185, p),
                op_ev("paged_attention.6", base + 185, base + 240, p),
                op_ev("fusion.7", base + 240, base + 300, p),
                op_ev("copy.11", base + 300, base + 330, p)]
    dev += [op_ev("fusion.7", 1340, 1360, p,
                  head="%fusion.7 = f32[1,512]{1,0} fusion("),
            op_ev("copy.11", 700, 760, p)]
    dev.sort(key=lambda e: e.start)
    return tr.Trace(dev, host, 1), p


def test_decode_device_time_and_pool_passes_on_a_synthetic_trace():
    t, p = synthetic_trace()
    lo, hi = 0, 1500
    # the union of the program's ops in the two windows: 160-330 twice
    assert scopes.decode_device_ns(t, p, lo, hi) == pytest.approx(2 * 170)
    # pool passes: the loop's slice (its op_name has no part scope), the
    # page scatter (kv_pool) and the carry copy (no op_name), not the
    # pool-shaped paged_attention op nor the loop itself
    assert scopes.pool_pass_ns(t, p, lo, hi) == pytest.approx(
        2 * (20 + 5 + 30))
    by = scopes.ns_by_scope(t, p, lo, hi)
    assert by == {"kv_pool": 110.0, "paged_attention": 110.0, "mlp": 120.0}


def test_decode_readers_divide_by_the_steps_that_decoded(monkeypatch):
    t, p = synthetic_trace()
    monkeypatch.setattr(scopes, "run_program", lambda run: p)
    run = run_of(t, 0, 1500, 2)
    assert decode_device_ms.read(run) == pytest.approx(2 * 170e-6 / 2)
    assert kv_pool_pass_ms.read(run) == pytest.approx(2 * 55e-6 / 2)
    assert decode_device_ms.read(run_of(t, 0, 1500, 2, decoded=False)) \
        is None


def test_readers_read_nothing_from_a_trace_without_engine_phases(
        monkeypatch):
    old = tr.load(str(OLD_TRACE))
    lo, hi = tr.annotation_span(old, "bench.step")
    p = program()
    assert scopes.engine_idle_ns(old, lo, hi) is None
    assert scopes.idle_by_phase(old, lo, hi) is None
    assert scopes.decode_device_ns(old, p, lo, hi) is None
    assert scopes.pool_pass_ns(old, p, lo, hi) is None
    monkeypatch.setattr(scopes, "run_program", lambda run: p)
    run = run_of(old, lo, hi, 3)
    for reader in (engine_idle_ms, decode_device_ms, kv_pool_pass_ms):
        assert reader.read(run) is None
    # nor from a program without the scopes (one built before them)
    t, _ = synthetic_trace()
    bare = scopes.Program(scopes.parse_hlo(HLO.replace("decode_step/", "")),
                          POOLS)
    assert scopes.decode_device_ns(t, bare, 0, 1500) is None
    # a CPU run (no chip's peaks) reports no device number
    monkeypatch.undo()
    cpu = run_of(t, 0, 1500, 2)
    cpu.peaks = None
    for reader in (engine_idle_ms, decode_device_ms, kv_pool_pass_ms):
        assert reader.read(cpu) is None


def test_decode_hlo_is_the_engines_scoped_program():
    sys.path.insert(0, str(DATA))
    try:
        from make_engine_trace import CONF
    finally:
        sys.path.remove(str(DATA))
    conf = copy.deepcopy(CONF)
    text = scopes.decode_hlo(conf)
    prog = scopes.Program(scopes.parse_hlo(text), scopes.pool_dims(conf))
    assert prog.scoped
    found = {scopes.scope_of(n) for n in
             scopes._OP_NAME.findall(text)}
    assert set(scopes.SCOPES) <= found
    # the page scatter writes a layer's pool: a pool pass by both rules
    assert any(op.scope == "kv_pool" and op.dims in prog.pools
               for op in prog.ops.values())
