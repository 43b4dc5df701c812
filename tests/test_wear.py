"""Wear & write-energy telemetry: per-install cell flips must conserve
across the WearMap / ResidencyStats / metrics-histogram views, KV page
writes must match the actual device scatter + COW calls one for one, the
Gini summaries must stay in bounds on degenerate planes, the wear JSON
export must be byte-deterministic under a VirtualClock, and the bench
regression gate must flag direction-aware tolerance breaches."""
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.nn.model import init_params
from repro.serving import (EngineModel, SchedulerConfig, ServingEngine,
                           Tracer, VirtualClock, WearPlane, drive_simulated,
                           gini_coefficient)
from repro.serving.variants import perturbed_variant
from repro.streaming.delta import _cells, cell_delta_hist, flip_counts

MAX_SEQ = 48
CFG = get_config("gemma-7b", smoke=True)
PARAMS_A = init_params(jax.random.PRNGKey(0), CFG)
PARAMS_B = perturbed_variant(PARAMS_A)   # co-hosted fine-tune regime
N_PAGES = 24
PAGE = 8


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


def make_engine(*, reuse=True, paged=False, prefix_cache=False,
                clock=None, tracer=None, names=("a", "b")):
    clock = clock or VirtualClock()
    if paged:
        kv = dict(kv_slots=3, max_seq=MAX_SEQ, kv_layout="paged",
                  page_size=PAGE, n_pages=N_PAGES,
                  prefix_cache=prefix_cache)
    else:
        kv = dict(kv_slots=3, max_seq=MAX_SEQ)
    params = {"a": PARAMS_A, "b": PARAMS_B}
    eng = ServingEngine(
        [EngineModel(n, params[n], CFG, **kv) for n in names],
        weight_arena_slots=CFG.n_layers + 1,   # forces tenant swaps
        reuse=reuse,
        sched=SchedulerConfig(max_prefill_per_step=2),
        clock=clock, tracer=tracer)
    return eng, clock


# ------------------------------------------------------- flip semantics
@pytest.mark.parametrize("n", [1, 1000, (1 << 20) + 3])   # last: 2 chunks
def test_cell_delta_hist_matches_cell_expansion(n):
    """The joint byte-pair histogram counts exactly the per-cell level
    deltas that expanding every code into its 2-bit cells gives — the
    install accounting's integers do not depend on which one computes
    them."""
    rng = np.random.default_rng(n)
    old = rng.integers(0, 256, n).astype(np.uint8)
    new = np.clip(old.astype(int) + rng.integers(-3, 4, n), 0,
                  255).astype(np.uint8)
    for o, w in ((old, new), (None, new), (new, old)):
        d = _cells(w) - (0 if o is None else _cells(o))
        want = np.bincount(d.reshape(-1) + 3, minlength=7)
        np.testing.assert_array_equal(cell_delta_hist(o, w), want)


def test_flip_counts_semantics():
    rng = np.random.default_rng(0)
    old = rng.integers(0, 256, 64).astype(np.uint8)
    new = rng.integers(0, 256, 64).astype(np.uint8)

    # identity install programs nothing under equal-skip, everything cold
    assert flip_counts(old, old) == (0, 0)
    cells, pulses = flip_counts(old, old, skip_equal=False)
    assert cells == old.size * 4 and pulses == old.size * 4

    # cold install (erased region): every nonzero cell flips, pulses = Σ|Δ|
    cn = _cells(new)
    assert flip_counts(None, new) == (int(np.count_nonzero(cn)),
                                      int(cn.sum()))

    # delta install: equal-skip flips bounded by the raw rewrite, and
    # per-cell pulses never exceed the no-skip programmer's
    f_on, p_on = flip_counts(old, new)
    f_off, p_off = flip_counts(old, new, skip_equal=False)
    assert f_on <= f_off == new.size * 4
    assert p_on <= p_off

    # a new longer than old programs its tail from erased
    longer = np.concatenate([new, rng.integers(0, 256, 8).astype(np.uint8)])
    f_tail, p_tail = flip_counts(old, longer)
    f_head, p_head = flip_counts(old, new)
    f_cold, p_cold = flip_counts(None, longer[64:])
    assert (f_tail, p_tail) == (f_head + f_cold, p_head + p_cold)


# ------------------------------------------------------- gini bounds
def test_gini_bounds_and_degenerate():
    assert gini_coefficient([]) == 0.0
    assert gini_coefficient([7]) == 0.0
    assert gini_coefficient([0, 0, 0]) == 0.0
    assert gini_coefficient([5, 5, 5, 5]) == pytest.approx(0.0)
    # one-hot over n locations is the maximal spread: (n-1)/n
    for n in (2, 5, 32):
        one_hot = [0] * (n - 1) + [9]
        assert gini_coefficient(one_hot) == pytest.approx((n - 1) / n)
    rng = np.random.default_rng(3)
    for _ in range(10):
        g = gini_coefficient(rng.integers(0, 100, 50))
        assert 0.0 <= g <= 1.0

    # degenerate single-slot plane: every summary well-defined, gini 0
    plane = WearPlane("solo", 1)
    plane.record(0, flips=10, pulses=25)
    assert plane.gini("writes") == 0.0
    assert plane.summary()["gini_flips"] == 0.0
    assert plane.hottest() == [(0, 1)]
    json.dumps(plane.as_json())

    with pytest.raises(ValueError):
        WearPlane("empty", 0)
    with pytest.raises(KeyError):
        plane.counts("joules")


# ------------------------------------- flip conservation & reuse energy
def test_flip_conservation_and_reuse_energy():
    jobs = two_tenant_jobs()
    arms = {}
    for reuse in (False, True):
        eng, clock = make_engine(reuse=reuse)
        summary = drive_simulated(eng, clock, jobs, max_steps=10_000)
        arms[reuse] = (eng, summary)

    eng, summary = arms[True]
    stats = eng.residency.stats
    plane = eng.wear.plane("weight")
    assert stats.installs > 0

    # every flip/pulse recorded in _install lands in exactly one slot of
    # the wear plane, one histogram sample, and the stats totals
    assert int(plane.flips.sum()) == stats.cell_flips
    assert int(plane.pulses.sum()) == stats.write_pulses
    assert int(plane.writes.sum()) == stats.installs
    by_group = plane.by_group
    assert sum(v[1] for v in by_group.values()) == stats.cell_flips
    assert sum(v[0] for v in by_group.values()) == stats.installs
    hist = eng.metrics.registry.histogram("install_cell_flips")
    assert hist.count == stats.installs
    assert int(hist.sum) == stats.cell_flips

    # summary wiring: energy is exactly pulses × the model's pulse joules
    assert summary["install_cell_flips"] == float(stats.cell_flips)
    assert summary["install_energy_j"] == pytest.approx(
        stats.write_pulses * eng.energy_model.write_pulse_j)
    assert 0.0 <= summary["wear_gini_weight"] <= 1.0
    assert "wear_gini_kv" not in summary   # slot arenas: no KV write plane

    # same virtual-clock schedule across arms (installs are instant and
    # decode runs the full-precision params), so the equal-skip programmer
    # must spend strictly less write energy than the rewrite-everything one
    eng_off, s_off = arms[False]
    assert s_off["steps"] == summary["steps"]
    assert {r.rid: r.generated for r in eng_off.requests.values()} == \
        {r.rid: r.generated for r in eng.requests.values()}
    assert summary["install_energy_j"] < s_off["install_energy_j"]


# ----------------------------------------------- KV page write accounting
def test_kv_page_writes_match_scatter_cow_events():
    eng, clock = make_engine(paged=True, prefix_cache=True, names=("a",))
    arena = eng.arenas["a"]
    calls = {"write": 0, "copy": 0}
    orig_write, orig_copy = arena._write, arena._copy

    def counting_write(*a):
        calls["write"] += 1
        return orig_write(*a)

    def counting_copy(*a):
        calls["copy"] += 1
        return orig_copy(*a)

    arena._write, arena._copy = counting_write, counting_copy

    # two identical 20-token prompts arriving together: the second shares
    # all 3 pages of the first (exact-tuple tail edge), then both decode
    # into the shared partial block at pos 20 — forcing exactly one COW
    rng = np.random.default_rng(5)
    twin = rng.integers(1, CFG.vocab, 20).tolist()
    jobs = [(0.0, "a", twin, 6), (0.0, "a", list(twin), 6)]
    for i in range(4):
        jobs.append((2.0 + i, "a", rng.integers(1, CFG.vocab, 7).tolist(),
                     int(rng.integers(4, 8))))
    summary = drive_simulated(eng, clock, jobs, max_steps=10_000)
    assert summary["requests_finished"] == len(jobs)

    # every accounted page write is one real device scatter or COW copy
    assert arena.kv_page_writes == calls["write"] + calls["copy"]
    assert calls["copy"] == arena.allocator.cow_copies >= 1
    assert arena.kv_page_writes_avoided >= 3   # the twin's shared pages

    plane = eng.wear.plane("kv:a")
    assert plane.first == 1                    # scratch page 0 untracked
    assert int(plane.writes.sum()) == arena.kv_page_writes
    assert summary["kv_page_writes"] == float(arena.kv_page_writes)
    assert summary["kv_page_writes_avoided"] == float(
        arena.kv_page_writes_avoided)
    assert summary["kv_write_energy_j"] == pytest.approx(
        eng.energy_model.kv_write_j(arena.kv_bytes_written))


# --------------------------------------------------- deterministic export
def test_wear_json_deterministic():
    docs = []
    for _ in range(2):
        eng, clock = make_engine(paged=True, prefix_cache=True)
        drive_simulated(eng, clock, two_tenant_jobs(seed=2), max_steps=10_000)
        assert set(eng.wear.planes) == {"weight", "kv:a", "kv:b"}
        docs.append(json.dumps(eng.wear.as_json(), sort_keys=True))
    assert docs[0] == docs[1]


# -------------------------------------------------------- trace counters
def test_trace_counter_tracks():
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    eng, _ = make_engine(paged=True, tracer=tracer, clock=clock)
    drive_simulated(eng, clock, two_tenant_jobs(seed=4, n=6),
                    max_steps=10_000)
    counters = {e["name"] for e in tracer.chrome_trace_doc()["traceEvents"]
                if e.get("ph") == "C"}
    assert {"install_flips", "wear_gini_weight", "kv_free_pages",
            "install_queue_depth"} <= counters


# ----------------------------------------------------- junit properties
def test_wear_junit_properties(record_property):
    jobs = two_tenant_jobs(seed=6, n=8)
    arms = {}
    for reuse in (False, True):
        eng, clock = make_engine(reuse=reuse, paged=True, prefix_cache=True)
        arms[reuse] = drive_simulated(eng, clock, jobs, max_steps=10_000)
    on, off = arms[True], arms[False]
    assert on["install_energy_j"] < off["install_energy_j"]
    record_property("install_energy_mj_on", on["install_energy_j"] * 1e3)
    record_property("install_energy_mj_off", off["install_energy_j"] * 1e3)
    record_property("install_cell_flips", on["install_cell_flips"])
    record_property("kv_write_energy_mj", on["kv_write_energy_j"] * 1e3)
    record_property("kv_page_writes", on["kv_page_writes"])
    record_property("wear_gini_weight", on["wear_gini_weight"])
    record_property("wear_gini_kv", on["wear_gini_kv"])


# ------------------------------------------------------ regression gate
def _load_gate():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "check_regression.py")
    spec = importlib.util.spec_from_file_location("check_regression", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _doc(**wear):
    return {"parts": {"wear": wear}}


def test_regression_gate_directions(tmp_path):
    gate = _load_gate()
    base = _doc(install_energy_j_on=1.0, kv_page_writes=10.0,
                wear_gini_weight=0.4)
    base["parts"]["overlap"] = {"stall_steps_overlap": 4.0,
                                "hidden_bytes": 100.0}

    def run(fresh):
        bp, fp = tmp_path / "base.json", tmp_path / "fresh.json"
        bp.write_text(json.dumps(base))
        fp.write_text(json.dumps(fresh))
        return gate.main(["--baseline", str(bp), "--fresh", str(fp)])

    # identical and better-on-every-axis both pass
    assert run(base) == 0
    better = _doc(install_energy_j_on=0.5, kv_page_writes=8.0,
                  wear_gini_weight=0.3)
    better["parts"]["overlap"] = {"stall_steps_overlap": 2.0,
                                  "hidden_bytes": 150.0}
    assert run(better) == 0

    # within-tolerance drift passes; past-tolerance fails, each direction
    drift = json.loads(json.dumps(base))
    drift["parts"]["wear"]["install_energy_j_on"] = 1.05   # 10% tol
    assert run(drift) == 0
    worse_lower = json.loads(json.dumps(base))
    worse_lower["parts"]["wear"]["install_energy_j_on"] = 1.2
    assert run(worse_lower) == 1
    worse_higher = json.loads(json.dumps(base))
    worse_higher["parts"]["overlap"]["hidden_bytes"] = 80.0  # higher=better
    assert run(worse_higher) == 1
    worse_exact = json.loads(json.dumps(base))
    worse_exact["parts"]["overlap"]["stall_steps_overlap"] = 5.0  # 0% tol
    assert run(worse_exact) == 1

    # --warn-only reports but exits 0; missing metrics are skipped, a
    # fully disjoint doc is an input error
    bp, fp = tmp_path / "b2.json", tmp_path / "f2.json"
    bp.write_text(json.dumps(base))
    fp.write_text(json.dumps(worse_lower))
    assert gate.main(["--baseline", str(bp), "--fresh", str(fp),
                      "--warn-only"]) == 0
    fp.write_text(json.dumps({"parts": {"layout": {"x": 1.0}}}))
    assert gate.main(["--baseline", str(bp), "--fresh", str(fp)]) == 2

    rows = gate.compare(base["parts"], worse_lower["parts"])
    bad = [r for r in rows if r["regressed"]]
    assert [(r["part"], r["metric"]) for r in bad] == \
        [("wear", "install_energy_j_on")]


def test_regression_gate_on_committed_trajectory():
    gate = _load_gate()
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_serving.json")
    with open(path) as f:
        parts = json.load(f)["parts"]
    rows = gate.compare(parts, parts)
    assert rows, "committed trajectory shares no gated metrics with SPECS"
    assert not any(r["regressed"] for r in rows)
    gated_parts = {r["part"] for r in rows}
    assert "wear" in gated_parts, \
        "committed BENCH_serving.json is missing the wear part"
