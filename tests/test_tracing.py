"""Structured tracing: a simulated two-tenant run must produce a valid,
deterministic Chrome-trace document with balanced request lifecycles; the
per-step component breakdown must account (exactly, under virtual time)
for step wall time; the typed metrics registry must reproduce the legacy
EngineMetrics quantile behaviour; and the disabled-tracer path must stay
allocation-free so instrumentation is safe to leave in hot loops.  An
enabled tracer's spans also land on the profiler's host line as
`engine.<component>`, and the decode program's named scopes are metadata
that leave its compiled HLO as it was."""
import contextlib
import json
import math
import re
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.steps import cached_fused_paged_serve_step
from repro.nn.model import init_params
from repro.serving import (EngineMetrics, EngineModel, InstallCostModel,
                           MetricsRegistry, NULL_TRACER, NullTracer,
                           SchedulerConfig, ServingEngine, Tracer,
                           VirtualClock, WeightResidencyManager,
                           drive_simulated)
from repro.serving.paging import init_page_pool
from repro.serving.tracing import (_NULL_SPAN, REQUEST_PHASES,
                                   TRACE_COMPONENTS)

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench import trace_reduce  # noqa: E402

MAX_SEQ = 32
CFG = get_config("gemma-7b", smoke=True)
PARAMS_A = init_params(jax.random.PRNGKey(0), CFG)
PARAMS_B = init_params(jax.random.PRNGKey(1), CFG)
N_JOBS = 8


def two_tenant_jobs(seed=0, n=N_JOBS):
    rng = np.random.default_rng(seed)
    t, jobs = 0.0, []
    for i in range(n):
        t += float(rng.exponential(1.5))
        plen = int(rng.integers(3, 10))
        jobs.append((t, "a" if i % 2 == 0 else "b",
                     rng.integers(1, CFG.vocab, plen).tolist(),
                     int(rng.integers(4, 8))))
    return jobs


def make_engine(tracer=None, clock=None):
    clock = clock or VirtualClock()
    eng = ServingEngine(
        [EngineModel("a", PARAMS_A, CFG, kv_slots=3, max_seq=MAX_SEQ),
         EngineModel("b", PARAMS_B, CFG, kv_slots=3, max_seq=MAX_SEQ)],
        weight_arena_slots=CFG.n_layers + 1,   # can't co-host: turn switches
        sched=SchedulerConfig(max_prefill_per_step=2),
        clock=clock, tracer=tracer)
    return eng, clock


def traced_run(seed=0):
    """Two-tenant simulated run with the tracer on the same VirtualClock."""
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    eng, _ = make_engine(tracer=tracer, clock=clock)
    summary = drive_simulated(eng, clock, two_tenant_jobs(seed),
                              max_steps=10_000)
    return eng, tracer, summary


# ------------------------------------------------------------ trace schema
def test_trace_schema_and_balanced_request_lifecycles():
    eng, tracer, summary = traced_run()
    assert summary["requests_finished"] == N_JOBS
    assert not tracer._open_phase, "a lifecycle span was left open"

    doc = tracer.chrome_trace_doc()
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    body = [e for e in evs if e["ph"] != "M"]
    assert body, "trace is empty"

    # process/thread metadata: both pids named, every tid used is named
    pnames = {(e["pid"], e["args"]["name"])
              for e in meta if e["name"] == "process_name"}
    assert (0, "engine") in pnames and (1, "requests") in pnames
    named = {(e["pid"], e["tid"]) for e in meta if e["name"] == "thread_name"}
    for e in body:
        assert e["ph"] in ("X", "i", "C")
        assert isinstance(e["ts"], (int, float))
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] in ("g", "t")
        if "tid" in e:
            assert isinstance(e["tid"], int), "Chrome tids must be integers"
            assert (e["pid"], e["tid"]) in named, f"unnamed tid in {e}"

    # component spans live on pid 0 under canonical component names
    comp_spans = [e for e in body if e["pid"] == 0 and e["ph"] == "X"]
    assert comp_spans
    tid_names = {(e["pid"], e["tid"]): e["args"]["name"]
                 for e in meta if e["name"] == "thread_name"}
    for e in comp_spans:
        assert tid_names[(0, e["tid"])] in TRACE_COMPONENTS

    # request lifecycles: every request starts queued and ends finished,
    # with only known phases in between and non-overlapping spans
    per_req = {}
    for e in body:
        if e["pid"] == 1:
            per_req.setdefault(e["tid"], []).append(e)
    assert len(per_req) == N_JOBS
    for seq in per_req.values():
        names = [e["name"] for e in seq if not e["name"].endswith(":enter")]
        assert names[0] == "queued"
        assert names[-1] == "finished"
        assert set(names) <= set(REQUEST_PHASES)
        spans = [e for e in seq if e["ph"] == "X"]
        for a, b in zip(spans, spans[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-6

    # under virtual time the clock never advances inside a step, so the
    # component breakdown accounts for step wall time *exactly* (both 0)
    assert eng.metrics.steps
    for rec in eng.metrics.steps:
        assert set(rec.component_s) <= set(TRACE_COMPONENTS)
        assert sum(rec.component_s.values()) == 0.0

    # summary surfaces per-component totals
    assert any(k.startswith("component_") for k in summary)


def test_wall_clock_component_times_sum_within_step_wall_time():
    # engine on virtual time (deterministic schedule), tracer on the wall
    # clock: each step's component sum must be positive and bounded by the
    # step's measured wall time (components are disjoint sub-intervals)
    tracer = Tracer()   # wall clock
    eng, clock = make_engine(tracer=tracer)
    walls, t0 = [], [0.0]
    drive_simulated(
        eng, clock, two_tenant_jobs(n=4), max_steps=10_000,
        before_step=lambda e: t0.__setitem__(0, time.perf_counter()),
        after_step=lambda e: walls.append(time.perf_counter() - t0[0]))
    assert len(walls) == len(eng.metrics.steps)
    for rec, wall in zip(eng.metrics.steps, walls):
        comp = sum(rec.component_s.values())
        assert comp > 0.0
        assert comp <= wall + 1e-4


def test_virtual_clock_traces_are_byte_identical_across_runs():
    _, t1, s1 = traced_run(seed=2)
    _, t2, s2 = traced_run(seed=2)
    assert s1 == s2
    j1, j2 = t1.to_chrome_json(), t2.to_chrome_json()
    assert j1 == j2, "virtual-clock trace is not deterministic"
    json.loads(j1)   # well-formed JSON document


def test_request_timeline_renders_phase_history():
    tracer = Tracer(clock=VirtualClock())
    tracer.request_phase(7, "queued")
    tracer.request_phase(7, "prefilling")
    tracer.request_phase(7, "running")
    line = tracer.request_timeline(7)
    assert "queued=" in line and "prefilling=" in line
    assert line.endswith("*"), "open phase should be starred"
    tracer.request_phase(7, "finished")
    assert "*" not in tracer.request_timeline(7)
    assert tracer.request_timeline(999) == "(no spans)"


# ------------------------------------------------------- metrics registry
def test_registry_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("toks")
    c.inc()
    c.inc(5)
    assert c.value == 6 and isinstance(c.value, int)
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("depth")
    g.set(3)
    g.set(1)
    assert g.value == 1 and g.max == 3

    h = reg.histogram("lat")
    assert math.isnan(h.quantile(50))
    assert math.isnan(h.mean())
    for v in range(1, 101):
        h.observe(float(v))
    # np.percentile linear interpolation, exactly the legacy _pct helper
    assert h.quantile(50) == pytest.approx(50.5)
    assert h.quantile(95) == pytest.approx(95.05)
    assert h.quantile(95) == pytest.approx(
        float(np.percentile(np.arange(1.0, 101.0), 95)))
    assert h.count == 100 and h.sum == pytest.approx(5050.0)

    # get-or-create returns the same instrument; type conflicts are errors
    assert reg.counter("toks") is c
    with pytest.raises(TypeError):
        reg.gauge("toks")

    d = reg.as_dict()
    assert d["toks"] == 6.0
    assert d["depth"] == 1.0 and d["depth_max"] == 3.0
    assert d["lat_count"] == 100.0 and d["lat_p50"] == pytest.approx(50.5)


def test_engine_metrics_empty_window_quantiles_are_nan():
    m = EngineMetrics()
    s = m.summary(1.0)
    for key in ("latency_p50_s", "latency_p95_s", "ttft_p50_s",
                "ttft_p95_s", "itl_max_p50_s", "itl_max_p95_s"):
        assert math.isnan(s[key]), f"{key} should be NaN with no requests"
    assert s["requests_finished"] == 0.0
    # registry export mirrors the same empty-window behaviour
    d = m.registry.as_dict()
    assert d["request_ttft_s_count"] == 0.0
    assert math.isnan(d["request_ttft_s_p95"])


# ------------------------------------------------------ disabled-path cost
def test_null_tracer_is_allocation_free():
    t = NULL_TRACER
    assert isinstance(t, NullTracer)
    assert t.enabled is False
    assert NullTracer.__slots__ == ()   # no per-instance dict either
    # every span call returns the one shared no-op context manager: the
    # disabled path allocates no span or event objects at all
    s = t.span("decode", step=3)
    assert s is t.span("sample") is _NULL_SPAN
    with s:
        pass
    assert t.instant("kv_evict", pages=4) is None
    assert t.counter("queue_depth", 7) is None
    assert t.request_phase(0, "queued") is None
    assert t.step_components() == {}
    assert t.request_timeline(0) == ""
    assert not hasattr(t, "events")
    with pytest.raises(RuntimeError):
        t.export_chrome_trace("/dev/null")


def test_serving_headline_junit_properties(record_property):
    """Virtual-clock two-tenant run with budgeted synchronous installs,
    publishing the serving headline numbers (ttft p95, worst inter-token
    gap p95, install stall steps, prefix hit rate, trace size) as junit
    <properties> — CI re-runs this test in a named step so the numbers
    surface per workflow run alongside the BENCH_serving.json artifact."""
    probe = WeightResidencyManager(
        {"a": (PARAMS_A, CFG), "b": (PARAMS_B, CFG)}, CFG.n_layers)
    bpt = max(max(lw.codes.size for lw in probe.store.layers) // 2, 1)
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    eng = ServingEngine(
        [EngineModel("a", PARAMS_A, CFG, kv_slots=3, max_seq=MAX_SEQ),
         EngineModel("b", PARAMS_B, CFG, kv_slots=3, max_seq=MAX_SEQ)],
        weight_arena_slots=CFG.n_layers + 1,
        sched=SchedulerConfig(max_prefill_per_step=2),
        clock=clock, tracer=tracer,
        install_ticks_per_step=1,
        install_cost=InstallCostModel(bytes_per_tick=bpt))
    s = drive_simulated(eng, clock, two_tenant_jobs(), max_steps=10_000)
    assert s["requests_finished"] == N_JOBS
    # tick-budgeted synchronous installs pay every tenant switch in full
    assert s["install_stall_steps"] > 0
    record_property("ttft_p95_ms", round(s["ttft_p95_s"] * 1e3, 3))
    record_property("itl_max_p95_ms", round(s["itl_max_p95_s"] * 1e3, 3))
    record_property("install_stall_steps", int(s["install_stall_steps"]))
    record_property("prefix_hit_rate", round(s["prefix_hit_rate"], 4))
    record_property("trace_events", len(tracer.events))


def test_untraced_engine_records_empty_component_breakdowns():
    eng, clock = make_engine()   # no tracer: engine keeps NULL_TRACER
    assert eng.tracer is NULL_TRACER
    drive_simulated(eng, clock, two_tenant_jobs(n=2), max_steps=10_000)
    assert eng.metrics.steps
    for rec in eng.metrics.steps:
        assert rec.component_s == {}


# ------------------------------------------------ on the profiler's clock
def profiled_paged_steps(directory, tracer=None, n_steps=3):
    """A one-tenant paged engine whose first two steps (prefill, decode
    compile) run unprofiled; the next `n_steps` are profiled, each inside
    a `bench.step` annotation as `bench/harness.py` makes them.
    Returns (engine, loaded trace)."""
    eng = ServingEngine(
        [EngineModel("a", PARAMS_A, CFG, kv_slots=2, kv_layout="paged",
                     page_size=4, n_pages=16)], tracer=tracer)
    eng.submit("a", [3, 1, 4, 1, 5], max_new_tokens=n_steps + 4)
    eng.submit("a", [2, 7, 1], max_new_tokens=n_steps + 4)
    eng.step()
    eng.step()
    jax.profiler.start_trace(str(directory))
    try:
        for _ in range(n_steps):
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
    finally:
        jax.profiler.stop_trace()
    return eng, trace_reduce.load(trace_reduce.find_xplane(str(directory)))


def test_engine_phases_land_on_the_profiler_host_line(tmp_path):
    eng, tr = profiled_paged_steps(tmp_path, tracer=Tracer())
    steps = [ev for ev in tr.host if ev.name == "bench.step"]
    assert len(steps) == 3
    for step, rec in zip(steps, eng.metrics.steps[-3:]):
        inside = [ev.name for ev in tr.host
                  if ev.name.startswith("engine.")
                  and step.start <= ev.start and ev.end <= step.end]
        # every component the step ran, and nothing else, nested in it
        assert {n[len("engine."):] for n in inside} == set(rec.component_s)
        assert {"schedule", "inputs", "decode", "sample"} <= set(
            rec.component_s)
    # no engine annotation falls outside a step
    n_engine = sum(1 for ev in tr.host if ev.name.startswith("engine."))
    assert n_engine == sum(len(r.component_s)
                           for r in eng.metrics.steps[-3:])


def test_null_tracer_records_no_engine_annotations(tmp_path):
    eng, tr = profiled_paged_steps(tmp_path)
    assert eng.tracer is NULL_TRACER
    assert sum(1 for ev in tr.host if ev.name == "bench.step") == 3
    assert not [ev for ev in tr.host if ev.name.startswith("engine.")]


def test_paged_decode_step_times_its_sampler_inputs():
    tracer = Tracer()
    eng = ServingEngine(
        [EngineModel("a", PARAMS_A, CFG, kv_slots=2, kv_layout="paged",
                     page_size=4, n_pages=16)], tracer=tracer)
    eng.submit("a", [3, 1, 4], max_new_tokens=4)
    eng.run()
    decoded = [r for r in eng.metrics.steps if r.n_decoded]
    assert decoded
    for rec in decoded:
        assert "inputs" in rec.component_s
        assert set(rec.component_s) <= set(TRACE_COMPONENTS)
    assert "inputs" in TRACE_COMPONENTS


def test_span_annotations_nest_and_close_on_error():
    log = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name, exc[0]))

    tracer = Tracer()
    tracer._annotate = Annotation
    with pytest.raises(ValueError):
        with tracer.span("decode"):
            with tracer.span("page"):
                raise ValueError("boom")
    assert log == [("enter", "engine.decode"), ("enter", "engine.page"),
                   ("exit", "engine.page", ValueError),
                   ("exit", "engine.decode", ValueError)]
    # the tracer's own record is kept as before
    assert set(tracer.step_components()) == {"decode", "page"}
    assert [e["name"] for e in tracer.events] == ["page", "decode"]


def _instructions(hlo: str):
    """The HLO's instruction lines without their metadata."""
    return [re.sub(r",? ?metadata=\{[^}]*\}", "", line)
            for line in hlo.splitlines()
            if re.match(r"\s*(ROOT )?%[\w.\-]+ = ", line)]


def test_decode_scopes_leave_the_program_unchanged(monkeypatch):
    pcfg = CFG
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), pcfg))
    pools = jax.eval_shape(lambda: init_page_pool(pcfg, 17, 4))
    i32 = jax.ShapeDtypeStruct((2,), np.int32)
    args = (params, i32, pools, i32, jax.ShapeDtypeStruct((2, 16), np.int32),
            jax.ShapeDtypeStruct((2,), np.float32), i32,
            jax.ShapeDtypeStruct((2, 2), np.uint32), i32)

    def optimized():
        # a fresh jit of the engine's step: traced and compiled anew
        fn = cached_fused_paged_serve_step.__wrapped__(pcfg, "xla", False)
        return fn.lower(*args).compile().as_text()

    scoped = optimized()
    assert 'op_name="jit(fused_paged_serve_step)/decode_step/' in scoped
    names = set(re.findall(r'op_name="[^"]*/decode_step/([^"]*)"', scoped))
    parts = {p for n in names for p in n.split("/")}
    assert {"embed", "attn_proj", "kv_pool", "paged_attention", "mlp",
            "lm_head", "sample"} <= parts
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = optimized()
    assert "/decode_step/" not in plain
    assert _instructions(scoped) == _instructions(plain)
