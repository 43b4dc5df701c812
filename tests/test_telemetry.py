"""Live telemetry plane: streaming quantiles (P2 + sliding window) must
track `np.percentile` (exactly when the stream fits the window, within
tolerance for the lifetime estimator), the burn-rate SLO tracker must
transition breach -> recover deterministically, and the engine-level
plane (windows + SLO + flight recorder + watchdog) must be pure
observation: token-identical to a defaults-off run on the same schedule,
with `health()` snapshots and flight dumps byte-identical across runs
under `VirtualClock`."""
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.nn.model import init_params
from repro.serving import (EngineModel, FlightRecorder, P2Quantile,
                           SchedulerConfig, ServingEngine, SLOConfig,
                           SLOTracker, SlidingWindow, StreamStat, Tracer,
                           TelemetryConfig, VirtualClock, drive_simulated,
                           prometheus_text, validate_events_jsonl,
                           validate_prometheus_text)
from repro.serving.telemetry import dumps_deterministic
from repro.serving.variants import perturbed_variant

MAX_SEQ = 48
CFG = get_config("gemma-7b", smoke=True)
PARAMS_A = init_params(jax.random.PRNGKey(0), CFG)
PARAMS_B = perturbed_variant(PARAMS_A)
N_PAGES = 24
PAGE = 8

# an ITL target far below the virtual step dt: every decode interval is
# over-limit, so the burn windows saturate and the breach fires early.
# (TTFT can NOT force a breach here: under VirtualClock a request that
# prefills the same step it arrives has ttft exactly 0.0, and the
# over-limit indicator is strict.)
TIGHT_ITL = SLOConfig(itl_p95_s=1e-3)
STEP_DT = 0.01


def two_tenant_jobs(seed=0, n=10):
    rng = np.random.default_rng(seed)
    t, jobs = 0.0, []
    for i in range(n):
        t += float(rng.exponential(1.5))
        plen = int(rng.integers(3, 10))
        jobs.append((t, "a" if i % 2 == 0 else "b",
                     rng.integers(1, CFG.vocab, plen).tolist(),
                     int(rng.integers(4, 8))))
    return jobs


def make_engine(*, clock=None, tracer=None, **knobs):
    clock = clock or VirtualClock()
    kv = dict(kv_slots=3, max_seq=MAX_SEQ, kv_layout="paged",
              page_size=PAGE, n_pages=N_PAGES, prefix_cache=True)
    eng = ServingEngine(
        [EngineModel(n, {"a": PARAMS_A, "b": PARAMS_B}[n], CFG, **kv)
         for n in ("a", "b")],
        weight_arena_slots=CFG.n_layers + 2,
        sched=SchedulerConfig(max_prefill_per_step=2),
        clock=clock, tracer=tracer, **knobs)
    return eng, clock


def generated_by_rid(eng):
    return {r.rid: tuple(r.generated) for r in eng.requests.values()}


# ------------------------------------------------------- quantile maths
# P2 guarantees exactness below 5 samples and an estimate inside the
# sample range after that; closeness to the true quantile comes only once
# its markers have converged.  A property sweep over arbitrary streams
# finds 20-sample streams whose median estimate sits a third of the range
# away, and none from 50 samples on; 100 leaves a margin.
P2_CONVERGED = 100


def _quantile_invariants(samples, window, accurate_from=5):
    """Shared property body: windowed quantiles are exact `np.percentile`
    over the tail; the lifetime P2 estimate is exact below 5 samples,
    inside [min, max] from there, and within tolerance of the truth from
    `accurate_from` samples on."""
    stat = StreamStat(window=window)
    for x in samples:
        stat.observe(float(x))
    tail = np.asarray(samples[-window:], dtype=float)
    snap = stat.snapshot()
    assert snap["n"] == len(samples)
    assert snap["last"] == pytest.approx(float(samples[-1]))
    # the sliding window is exact, whatever the stream length
    assert snap["p50"] == pytest.approx(np.percentile(tail, 50))
    assert snap["p95"] == pytest.approx(np.percentile(tail, 95))
    # P2 is exact below 5 samples (it keeps them all); for longer
    # streams it must stay inside the sample range, and near the truth
    # once converged
    full = np.asarray(samples, dtype=float)
    if len(samples) < 5:
        assert snap["stream_p50"] == pytest.approx(np.percentile(full, 50))
        assert snap["stream_p95"] == pytest.approx(np.percentile(full, 95))
    else:
        lo, hi = float(full.min()), float(full.max())
        span = max(hi - lo, 1e-12)
        assert lo <= snap["stream_p50"] <= hi
        assert lo <= snap["stream_p95"] <= hi
        if len(samples) >= accurate_from:
            assert abs(snap["stream_p50"] - np.percentile(full, 50)) \
                <= 0.25 * span
    assert stat.p50() == snap["p50"]


def test_quantiles_empty_and_small():
    stat = StreamStat(window=8)
    snap = stat.snapshot()
    assert snap["n"] == 0
    for k in ("last", "p50", "p95", "stream_p50", "stream_p95"):
        assert np.isnan(snap[k]), f"{k} must be NaN on an empty stream"
    # exact small-window behaviour, including n < 5 for P2
    for n in (1, 2, 3, 4):
        _quantile_invariants(list(range(n, 0, -1)), window=8)
    # single repeated value: every estimate collapses to it
    stat = StreamStat(window=4)
    for _ in range(32):
        stat.observe(2.5)
    snap = stat.snapshot()
    assert snap["p50"] == snap["p95"] == snap["stream_p95"] == 2.5

    win = SlidingWindow(window=3)
    assert np.isnan(win.quantile(50.0)) and np.isnan(win.last)
    for x in (5.0, 1.0, 3.0, 9.0):
        win.observe(x)
    assert len(win) == 3 and win.total == 4          # ring evicted the 5.0
    assert win.quantile(50.0) == pytest.approx(3.0)

    with pytest.raises(ValueError):
        SlidingWindow(0)
    with pytest.raises(ValueError):
        P2Quantile(0.0)


def test_p2_converges_on_large_stream():
    """Lifetime P2 p95 lands within ~2% of np.percentile on a 5000-sample
    lognormal stream — the regime the 5-marker estimator is built for."""
    rng = np.random.default_rng(0)
    xs = rng.lognormal(0.0, 0.5, size=5000)
    q = P2Quantile(0.95)
    for x in xs:
        q.observe(float(x))
    truth = float(np.percentile(xs, 95))
    assert q.value == pytest.approx(truth, rel=0.02)


def test_windowed_quantiles_property():
    """Hypothesis sweep of `_quantile_invariants` over random streams and
    window sizes, the P2 accuracy bound held from P2_CONVERGED samples."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(xs=st.lists(st.floats(-1e6, 1e6, allow_nan=False, width=32),
                       min_size=1, max_size=200),
           window=st.integers(1, 64))
    def prop(xs, window):
        _quantile_invariants(xs, window, accurate_from=P2_CONVERGED)

    prop()


def test_windowed_quantiles_manual_trials():
    """Deterministic fallback for environments without hypothesis: the
    same invariants over a seeded random sweep."""
    rng = np.random.default_rng(7)
    for _ in range(40):
        n = int(rng.integers(1, 200))
        window = int(rng.integers(1, 65))
        xs = rng.normal(0.0, float(rng.uniform(0.1, 100.0)), n).tolist()
        _quantile_invariants(xs, window)


# ------------------------------------------------------------ SLO maths
def test_slo_tracker_breach_and_recover():
    cfg = SLOConfig(ttft_p95_s=0.1, itl_p95_s=0.05,
                    short_window=4, long_window=8, min_samples=3)
    trk = SLOTracker(cfg)
    assert not trk.any_breached and trk.evaluate() == []

    # two bad ttft samples: under min_samples, no transition yet
    trk.observe("ttft_p95", 0.5)
    trk.observe("ttft_p95", 0.5)
    assert trk.evaluate() == []
    trk.observe("ttft_p95", 0.5)
    (kind, name, s, lo), = trk.evaluate()
    assert (kind, name) == ("slo_breach", "ttft_p95")
    assert s == 1.0 and lo == 1.0
    assert trk.any_breached
    assert trk.evaluate() == []               # transitions, not levels

    # good samples wash the short window first, then the long one
    for _ in range(8):
        trk.observe("ttft_p95", 0.01)
    (kind, name, s, lo), = trk.evaluate()
    assert (kind, name) == ("slo_recover", "ttft_p95")
    assert not trk.any_breached

    # untracked names are ignored; itl target untouched throughout
    trk.observe("nonsense", 99.0)
    st = trk.status()
    assert set(st) == {"ttft_p95", "itl_p95"}
    assert st["itl_p95"]["samples"] == 0
    assert st["ttft_p95"]["breached"] == 0
    assert SLOConfig().targets() == {}        # all-zero config: no targets


# ----------------------------------------------- engine: pure observation
def _drive(jobs, drive_kwargs=None, **knobs):
    # the tracer must share the virtual clock: trace timestamps and the
    # per-step component spans land in flight-recorder ring entries, so a
    # wall-clocked tracer would break dump byte-determinism
    clock = VirtualClock()
    eng, clock = make_engine(clock=clock, tracer=Tracer(clock=clock),
                             **knobs)
    drive_simulated(eng, clock, jobs, dt=STEP_DT, **(drive_kwargs or {}))
    return eng


def test_telemetry_token_identical_and_deterministic(tmp_path):
    """Everything on (windows + tight ITL SLO + recorder + watchdog) must
    decode the exact tokens of a defaults-off run, and two identical
    on-runs must produce byte-identical health snapshots, flight dumps
    and event logs even from different output directories."""
    jobs = two_tenant_jobs()
    plain = _drive(jobs)

    def run(d):
        os.makedirs(d, exist_ok=True)
        sampled = []
        eng = _drive(
            jobs,
            # sample the router probe mid-flight every 5 driven steps:
            # the sampled sequence must be byte-identical across runs too
            drive_kwargs=dict(health_every=5,
                              on_health=lambda h: sampled.append(h)),
            telemetry=TelemetryConfig(
                window=16, slo=TIGHT_ITL,
                events_path=os.path.join(d, "events.jsonl")),
            recorder=FlightRecorder(32, out_dir=str(d)),
            stall_timeout_s=300.0)
        eng.telemetry.close()
        return eng, sampled

    a, sampled_a = run(tmp_path / "a")
    b, sampled_b = run(tmp_path / "b")
    assert sampled_a, "health_every must sample the probe mid-run"
    assert [dumps_deterministic(h) for h in sampled_a] == \
        [dumps_deterministic(h) for h in sampled_b]

    assert generated_by_rid(a) == generated_by_rid(plain), \
        "telemetry plane changed decoded tokens"
    assert generated_by_rid(a) == generated_by_rid(b)

    # health snapshots: byte-identical canonical JSON
    ha, hb = a.health(), b.health()
    assert dumps_deterministic(ha) == dumps_deterministic(hb)
    assert ha["ok"] is False                  # tight ITL SLO is burning
    assert ha["slo"]["itl_p95"]["breached"] == 1
    assert ha["kv_total_pages"] == 2 * N_PAGES
    assert ha["queue_depth"] == 0 and ha["n_active"] == 0

    # the breach left exactly the same dump(s) in both directories
    assert a.recorder.dumps, "tight ITL SLO must leave a flight dump"
    assert [os.path.basename(p) for p in a.recorder.dumps] == \
        [os.path.basename(p) for p in b.recorder.dumps]
    for pa, pb in zip(a.recorder.dumps, b.recorder.dumps):
        with open(pa, "rb") as f:
            da = f.read()
        with open(pb, "rb") as f:
            db = f.read()
        assert da == db, f"{os.path.basename(pa)} differs across runs"
    dump = json.loads(da)
    assert dump["reason"] == "slo_breach"
    assert dump["entries"], "dump must carry the step ring"
    assert dump["n_entries"] <= 32

    # events JSONL: byte-identical and schema-valid
    ea = (tmp_path / "a" / "events.jsonl").read_bytes()
    assert ea == (tmp_path / "b" / "events.jsonl").read_bytes()
    assert validate_events_jsonl(ea.decode()) == []

    # windowed view saw every finish, globally and per tenant
    snap = a.telemetry.snapshot()
    assert snap["finishes"] == len(jobs)
    assert set(snap["tenants"]) == {"a", "b"}
    assert snap["global"]["itl_max_s"]["n"] == len(jobs)

    # Prometheus exposition from the live registry parses cleanly
    prom = prometheus_text(a.metrics.registry, a.telemetry)
    assert validate_prometheus_text(prom) == []
    assert 'repro_slo_breached{target="itl_p95"} 1' in prom
    assert "repro_engine_tokens_generated_total" in prom


def test_recorder_ring_and_fault_trigger(tmp_path):
    """A seeded fault run: every retirement dumps the ring (up to
    max_dumps), the ring never exceeds its bound, and the run still
    finishes every request."""
    eng = _drive(
        two_tenant_jobs(seed=1, n=8),
        fault_rate=0.02, fault_seed=11,
        recorder=FlightRecorder(4, out_dir=str(tmp_path), max_dumps=2))
    s = eng.metrics.summary(0.0)
    assert s["requests_finished"] == 8
    h = eng.health()
    retired = int(h["slots_retired"] + h["pages_retired"])
    assert retired > 0, "seeded 2% fault run must retire something"
    reasons = [t["reason"] for t in eng.recorder.triggers]
    assert reasons.count("unit_retired") == \
        len([t for t in eng.recorder.triggers]), reasons
    assert len(eng.recorder.dumps) == min(len(reasons), 2)  # max_dumps cap
    assert len(eng.recorder) <= 4
    doc = json.loads(open(eng.recorder.dumps[0]).read())
    assert doc["reason"] == "unit_retired"
    assert doc["attrs"]["retired_total"] >= 1
    # ring entries carry record + health + the step's trace events
    entry = doc["entries"][-1]
    assert {"step", "record", "health", "events"} <= set(entry)


def test_watchdog_stall_dump(tmp_path):
    """The watchdog path: a suspected stall emits the `stall_suspected`
    instant and a flight dump, and the fire is recorded on the engine's
    watchdog."""
    clock = VirtualClock()
    eng, clock = make_engine(
        clock=clock, tracer=Tracer(clock=clock),
        recorder=FlightRecorder(8, out_dir=str(tmp_path)),
        stall_timeout_s=300.0)
    drive_simulated(eng, clock, two_tenant_jobs(n=4), dt=STEP_DT)
    assert eng.watchdog is not None and eng.watchdog.fires == 0
    assert not eng.recorder.dumps            # healthy run: no dumps

    eng._on_stall(7)                         # what the timer thread runs
    assert [os.path.basename(p) for p in eng.recorder.dumps] == \
        ["flight-000-stall_suspected.json"]
    names = [e["name"] for e in eng.tracer.events
             if e.get("ph") == "i"]
    assert "stall_suspected" in names


def test_health_without_telemetry():
    """`health()` is a router probe even with every knob off: ok=True,
    capacity keys present, no `slo`/`windows` sections."""
    eng = _drive(two_tenant_jobs(n=4))
    h = eng.health()
    assert h["ok"] is True
    assert "slo" not in h and "windows" not in h
    assert h["kv_free_pages"] <= h["kv_total_pages"]
    assert h["weight_slots_total"] == CFG.n_layers + 2
    assert h["slots_retired"] == 0 and h["pages_retired"] == 0
    json.dumps(h)                            # snapshot is pure JSON


def test_per_tenant_summary_lines():
    eng = _drive(two_tenant_jobs())
    s = eng.metrics.summary(0.0)
    for name in ("a", "b"):
        assert s[f"tenant.{name}.requests"] == 5
        assert s[f"tenant.{name}.tokens_generated"] > 0
    from repro.serving.metrics import format_summary
    text = format_summary(s)
    assert "tenant a: 5 requests" in text
    assert "tenant b: 5 requests" in text


def test_telemetry_junit_properties(record_property):
    """Headline counters for the CI job summary."""
    jobs = two_tenant_jobs()
    eng = _drive(jobs, telemetry=TelemetryConfig(window=16, slo=TIGHT_ITL))
    snap = eng.telemetry.snapshot()
    record_property("telemetry_finishes", int(snap["finishes"]))
    record_property("telemetry_tenants", len(snap["tenants"]))
    record_property("slo_itl_breached",
                    int(snap["slo"]["itl_p95"]["breached"]))
    record_property("itl_p95_window_ms",
                    round(snap["global"]["itl_max_s"]["p95"] * 1e3, 3))
    assert snap["finishes"] == len(jobs)
