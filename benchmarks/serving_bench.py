"""Continuous-batching serving benchmark: Poisson-arrival multi-tenant
workload through `repro.serving.ServingEngine`.

Part 1 — two tenants share one device budget.  Tenant B is a perturbed
copy of tenant A (the fine-tuned-variant regime that multi-tenant weight
arenas actually see), so cross-tenant §V-C delta installs have real
structure to exploit.  The bench reports p50/p95 request latency, tokens/s,
queue depth, and the install wire bytes with cross-tenant reuse on vs off.

Part 2 — paged vs slot KV layout under mixed short/long Poisson traffic on
one tenant, at the SAME device KV budget.  The slot arm must size every
slot for the longest request, so short requests strand most of their slot;
the paged arm packs the same budget block by block, admits more requests
concurrently, and shares the pages of the common system-prompt prefix.

Part 3 — overlapped vs synchronous cross-tenant weight installs on a
deterministic virtual clock (simulated install ticks, so the numbers are
exactly reproducible).  Synchronous installs stall every tenant switch for
the whole install stream; the overlap arm pipelines the incoming tenant's
installs under the outgoing tenant's final decode steps (ARAS §IV applied
at the tenant scale) and must show strictly fewer install-stall steps and a
lower worst inter-token gap at the turn boundary — token-for-token
identical output.

Part 4 — chunked prefill with prompt-length bucketing on mixed 16–2048
token prompts, again on the virtual clock with a per-step cost model that
charges steps for the prompt tokens they prefill.  A monolithic prefill
burns a whole prompt in one step, so every concurrent decoder eats a
prompt-length inter-token gap; the chunked arm spreads the same tokens
across budgeted steps and must show a strictly lower worst decode ITL p95,
token-for-token identical.  The bucketing sub-arm counts distinct prefill
jit traces over randomized prompt lengths: bounded by the bucket ladder
with bucketing on, growing with every new tail length with it off.

Part 5 — radix-tree prefix cache on a shared-system-prompt multi-turn
workload (virtual clock, prefill-token cost model).  Every conversation
carries the same system prompt, and each turn's prompt is the previous
turn's prompt + generated reply + a fresh user message.  The cache-off arm
re-prefills that growing history from scratch every turn; the cache-on arm
retains finished requests' pages in the radix tree and skips every chunk
the cached prefix covers, so it must show strictly fewer computed prefill
tokens and a strictly lower TTFT p95 — token-for-token identical output.

Part 6 — per-step component breakdown through the structured tracer, on
the part-3 overlap workload.  A wall-clock `Tracer` on a virtual-clock
engine keeps the schedule deterministic while the component spans
(schedule / install / prefill / decode / sample / bookkeep) measure real
host seconds, printed as an overlap-on vs overlap-off table.  With
`--trace-out` it also re-runs the overlap arm with engine AND tracer on
one `VirtualClock` and writes the byte-identical Chrome-trace artifact.

Part 7 — wear & write energy: the part-1 reuse-on/off comparison re-run on
a virtual clock with paged KV + prefix cache on both tenants, priced in
joules through the ARAS energy model.  The schedule is identical across
arms (instant installs; reuse only changes install accounting), so the
reuse-on arm must spend strictly less install write energy — the §V-C
equal-skip pulses — while the prefix cache's avoided page writes and the
per-slot/per-page wear Gini are reported off the engine's WearMap.

Part 9 — kernel backend & fused sampling: the decode hot path run three
ways on one deterministic virtual-clock schedule with mixed greedy and
temperature/top-k requests.  The legacy arm decodes through the XLA
gather path and samples on the host; the fused arm keeps the XLA kernel
but samples inside the jitted step; the Pallas arm routes paged GQA
decode through the `kernels/paged_attention` kernel (interpret mode off
TPU) with fused sampling.  All three must be token-for-token identical,
every arm must spend at most one sampling host sync per decoded step
(the PR 9 per-row `int(argmax)` bug), and the tracer's component table
shows where the host seconds went.

Every run writes the per-part headline numbers to `BENCH_serving.json`
at the repo root (override with `--out`, disable with `--out ''`), so
the perf trajectory persists commit over commit.  `--parts` selects a
subset, e.g. the CI artifact job runs only the virtual-clock parts:

    PYTHONPATH=src python -m benchmarks.serving_bench
    PYTHONPATH=src python -m benchmarks.serving_bench --parts 3,6 \
        --trace-out trace.json
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from benchmarks.common import csv_row
from benchmarks.streaming_bench import _checkpointify
from repro.configs import get_config
from repro.launch.compile_cache import setup_compile_cache
from repro.nn.model import init_params
from repro.serving import (EngineModel, FlightRecorder, InstallCostModel,
                           SchedulerConfig, ServingEngine, SLOConfig,
                           TelemetryConfig, Tracer, VirtualClock,
                           WeightResidencyManager, drive_simulated,
                           format_summary, prometheus_text,
                           validate_events_jsonl, validate_prometheus_text)
from repro.serving.tracing import TRACE_COMPONENTS
from repro.serving.variants import perturbed_variant

N_REQUESTS = 24
ARRIVAL_RATE_HZ = 40.0      # Poisson arrival intensity
PROMPT_RANGE = (6, 20)
GEN_RANGE = (6, 14)
MAX_SEQ = 40
KV_SLOTS = 4
TURN_STEPS = 4


def _workload(seed: int = 0):
    rng = np.random.default_rng(seed)
    inter = rng.exponential(1.0 / ARRIVAL_RATE_HZ, N_REQUESTS)
    arrivals = np.cumsum(inter)
    jobs = []
    for i in range(N_REQUESTS):
        plen = int(rng.integers(*PROMPT_RANGE))
        gen = int(rng.integers(*GEN_RANGE))
        model = "base" if rng.random() < 0.5 else "variant"
        prompt = rng.integers(1, 500, plen).tolist()
        jobs.append((float(arrivals[i]), model, prompt, gen))
    return jobs


def _drive(eng, jobs):
    """Arrival-clocked driver: submit each job at its Poisson timestamp,
    stepping the engine whenever it has work."""
    t0 = time.perf_counter()
    pending = sorted(jobs)
    while pending or eng.has_work():
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            _, model, prompt, gen = pending.pop(0)
            eng.submit(model, prompt, max_new_tokens=gen)
        if eng.has_work():
            eng.step()
        elif pending:
            time.sleep(min(pending[0][0] - now, 1e-3))
    return eng.summary(time.perf_counter() - t0)


def _run_arm(cfg, params_a, params_b, jobs, *, reuse: bool):
    eng = ServingEngine(
        [EngineModel("base", params_a, cfg, kv_slots=KV_SLOTS,
                     max_seq=MAX_SEQ),
         EngineModel("variant", params_b, cfg, kv_slots=KV_SLOTS,
                     max_seq=MAX_SEQ)],
        weight_arena_slots=cfg.n_layers + 1,   # forces tenant swaps
        reuse=reuse,
        sched=SchedulerConfig(max_prefill_per_step=4,
                              model_turn_steps=TURN_STEPS))
    return _drive(eng, jobs)


# ---------------------------------------------------- paged vs slot layout
PAGE_SIZE = 8
LONG_MAX_SEQ = 96          # slot arm: every slot sized for the longest
SLOT_ARM_SLOTS = 4         # 4 × 96 tokens of KV budget
PAGED_ROWS = 8             # paged arm: same budget, finer admission
SYS_PREFIX_LEN = 16        # shared system prompt (2 full pages)
BURST_RATE_HZ = 400.0      # near-simultaneous arrivals: admission-bound


def _mixed_workload(seed: int = 1, n: int = 20):
    """Mostly-short burst traffic with a long tail, all behind one shared
    system prompt — the regime whole-sequence slots handle worst."""
    rng = np.random.default_rng(seed)
    sys_prefix = rng.integers(1, 500, SYS_PREFIX_LEN).tolist()
    arrivals = np.cumsum(rng.exponential(1.0 / BURST_RATE_HZ, n))
    jobs = []
    for i in range(n):
        if rng.random() < 0.25:        # long: ~2/3 of the slot ceiling
            plen, gen = int(rng.integers(40, 56)), int(rng.integers(12, 24))
        else:                          # short: strands a 96-token slot
            plen, gen = int(rng.integers(4, 12)), int(rng.integers(4, 10))
        prompt = sys_prefix + rng.integers(1, 500, plen).tolist()
        jobs.append((float(arrivals[i]), "base", prompt, gen))
    return jobs


def _run_layout_arm(cfg, params, jobs, *, layout: str):
    if layout == "paged":
        kv = dict(kv_slots=PAGED_ROWS, max_seq=LONG_MAX_SEQ,
                  kv_layout="paged", page_size=PAGE_SIZE,
                  n_pages=SLOT_ARM_SLOTS * LONG_MAX_SEQ // PAGE_SIZE)
    else:
        kv = dict(kv_slots=SLOT_ARM_SLOTS, max_seq=LONG_MAX_SEQ)
    eng = ServingEngine([EngineModel("base", params, cfg, **kv)],
                        sched=SchedulerConfig(max_prefill_per_step=4))
    return _drive(eng, jobs)


def paged_vs_slot() -> dict:
    print("\n== Paged vs slot KV layout (mixed short/long Poisson) ==")
    cfg = get_config("gemma-7b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    jobs = _mixed_workload()
    out = {}
    for layout in ("slot", "paged"):
        _run_layout_arm(cfg, params, jobs, layout=layout)   # jit warmup
        s = _run_layout_arm(cfg, params, jobs, layout=layout)
        out[layout] = s
        csv_row(f"serving/kv-{layout}", s["latency_p50_s"] * 1e6,
                f"p95_us={s['latency_p95_s']*1e6:.0f};"
                f"tok_s={s['tokens_per_s']:.1f};"
                f"max_conc={int(s['max_concurrent'])}")
        print(f"-- {layout} (KV budget "
              f"{SLOT_ARM_SLOTS * LONG_MAX_SEQ} tokens):")
        print(format_summary(s))
    sl, pg = out["slot"], out["paged"]
    print(f"-- same {SLOT_ARM_SLOTS * LONG_MAX_SEQ}-token KV budget: paged "
          f"admits {int(pg['max_concurrent'])} concurrent requests vs "
          f"{int(sl['max_concurrent'])} slots (queue depth max "
          f"{int(sl['queue_depth_max'])} -> {int(pg['queue_depth_max'])}), "
          f"saves {int(pg['kv_pages_saved'])} pages "
          f"({int(pg['kv_pages_saved']) * PAGE_SIZE} KV tokens) via shared "
          f"prefixes; p50 latency {sl['latency_p50_s']*1e3:.0f} vs "
          f"{pg['latency_p50_s']*1e3:.0f} ms "
          f"(smoke-scale CPU decode cost grows with the gather length — "
          f"the structural win is admission, occupancy, and sharing)")
    return out


# ------------------------------------------- overlapped installs (part 3)
OVERLAP_TURN_STEPS = 4
OVERLAP_STEP_DT = 1e-3      # one simulated engine step = 1 ms


def _overlap_workload(cfg, seed: int = 2, n: int = 16):
    """Two-tenant Poisson arrivals in *virtual* time (units of engine
    steps), long enough generations that turn rotations happen mid-flight."""
    rng = np.random.default_rng(seed)
    t, jobs = 0.0, []
    for i in range(n):
        t += float(rng.exponential(2.0)) * OVERLAP_STEP_DT
        plen = int(rng.integers(4, 12))
        jobs.append((t, "base" if i % 2 == 0 else "variant",
                     rng.integers(1, cfg.vocab, plen).tolist(),
                     int(rng.integers(8, 14))))
    return jobs


def _install_tick_bytes(cfg, params_a, params_b) -> int:
    """Size one install tick at half the biggest layer's raw stream so a
    cold tenant install spans several steps — the regime where hiding it
    matters.  (Sizing needs the quantized store, not a whole engine.)"""
    probe = WeightResidencyManager(
        {"base": (params_a, cfg), "variant": (params_b, cfg)}, cfg.n_layers)
    return max(max(lw.codes.size for lw in probe.store.layers) // 2, 1)


def _run_overlap_arm(cfg, params_a, params_b, jobs, *, overlap: bool,
                     bytes_per_tick: int, tracer=None, clock=None):
    clock = clock or VirtualClock()
    eng = ServingEngine(
        [EngineModel("base", params_a, cfg, kv_slots=KV_SLOTS,
                     max_seq=MAX_SEQ),
         EngineModel("variant", params_b, cfg, kv_slots=KV_SLOTS,
                     max_seq=MAX_SEQ)],
        weight_arena_slots=cfg.n_layers + 1,   # forces tenant swaps
        sched=SchedulerConfig(max_prefill_per_step=4,
                              model_turn_steps=OVERLAP_TURN_STEPS),
        clock=clock, tracer=tracer,
        install_ticks_per_step=1, overlap_installs=overlap,
        install_cost=InstallCostModel(bytes_per_tick=bytes_per_tick))
    summary = drive_simulated(eng, clock, jobs, dt=OVERLAP_STEP_DT)
    summary["_generated"] = {r.rid: list(r.generated)
                             for r in eng.requests.values()}
    return summary


def overlap_vs_sync() -> dict:
    print("\n== Overlapped vs synchronous weight installs "
          "(virtual clock, 2 tenants) ==")
    cfg = get_config("gemma-7b", smoke=True)
    params_a = _checkpointify(init_params(jax.random.PRNGKey(0), cfg))
    params_b = perturbed_variant(params_a)
    jobs = _overlap_workload(cfg)
    bpt = _install_tick_bytes(cfg, params_a, params_b)

    out = {}
    for overlap in (False, True):
        tag = "overlap-on" if overlap else "overlap-off"
        s = _run_overlap_arm(cfg, params_a, params_b, jobs, overlap=overlap,
                             bytes_per_tick=bpt)
        out[tag] = s
        csv_row(f"serving/install-{tag}", s["install_stall_steps"],
                f"hidden_mb={s['overlap_hidden_bytes']/1e6:.3f};"
                f"itl_p95_ms={s['itl_max_p95_s']*1e3:.1f};"
                f"steps={int(s['steps'])}")
        print(f"-- {tag}:")
        print(format_summary(s))
    sync, over = out["overlap-off"], out["overlap-on"]
    assert over["_generated"] == sync["_generated"], \
        "overlap changed decoded tokens"
    print(f"-- overlap hides {over['overlap_hidden_bytes']/1e6:.2f} MB of "
          f"install stream under decode: install stall steps "
          f"{int(sync['install_stall_steps'])} -> "
          f"{int(over['install_stall_steps'])}, worst inter-token gap p95 "
          f"{sync['itl_max_p95_s']*1e3:.1f} -> "
          f"{over['itl_max_p95_s']*1e3:.1f} ms, total steps "
          f"{int(sync['steps'])} -> {int(over['steps'])} "
          f"(token-for-token identical)")
    for s in out.values():
        s.pop("_generated")
    return out


# --------------------------------------------- chunked prefill (part 4)
CHUNK_STEP_DT = 1e-3        # one simulated engine step = 1 ms
CHUNK_TOKEN_COST = 2e-5     # + 20 µs of virtual step time per prefilled token
CHUNK_PROMPT_LENS = (16, 48, 2048, 24, 512, 96, 1024, 32)
CHUNK_SIZE = 128


def _chunk_workload(cfg, seed: int = 4):
    """Poisson arrivals of mixed short/long prompts on one tenant — the
    regime where one monolithic 2048-token prefill freezes every concurrent
    decode for two thousand token-times."""
    rng = np.random.default_rng(seed)
    t, jobs = 0.0, []
    for plen in CHUNK_PROMPT_LENS:
        t += float(rng.exponential(4.0)) * CHUNK_STEP_DT
        jobs.append((t, "base", rng.integers(1, cfg.vocab, plen).tolist(),
                     int(rng.integers(8, 14))))
    return jobs


def _run_chunk_arm(cfg, params, jobs, *, chunk: int, budget, growth=2.0,
                   max_seq: int = 2048 + 16):
    clock = VirtualClock()
    eng = ServingEngine(
        [EngineModel("base", params, cfg, kv_slots=4, max_seq=max_seq)],
        sched=SchedulerConfig(max_prefill_per_step=2,
                              prefill_token_budget=budget),
        clock=clock, prefill_chunk=chunk, bucket_growth=growth)
    summary = drive_simulated(
        eng, clock, jobs, dt=CHUNK_STEP_DT,
        step_dt=lambda rec: (CHUNK_STEP_DT
                             + CHUNK_TOKEN_COST * rec.prefill_tokens))
    summary["_generated"] = {r.rid: list(r.generated)
                             for r in eng.requests.values()}
    return summary


def chunked_prefill_bench() -> dict:
    print("\n== Chunked prefill + prompt-length bucketing "
          "(virtual clock, 16-2048 token prompts) ==")
    from repro.launch.steps import prefill_cache_info
    cfg = get_config("gemma-7b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    jobs = _chunk_workload(cfg)

    out = {}
    arms = {"chunk-off": dict(chunk=0, budget=None),
            "chunk-on": dict(chunk=CHUNK_SIZE, budget=CHUNK_SIZE)}
    for tag, kw in arms.items():
        s = _run_chunk_arm(cfg, params, jobs, **kw)
        out[tag] = s
        csv_row(f"serving/prefill-{tag}", s["itl_max_p95_s"] * 1e3,
                f"ttft_p95_ms={s['ttft_p95_s']*1e3:.1f};"
                f"chunks={int(s['prefill_chunks'])};"
                f"steps={int(s['steps'])}")
        print(f"-- {tag}:")
        print(format_summary(s))
    mono, chunked = out["chunk-off"], out["chunk-on"]
    assert mono["_generated"] == chunked["_generated"], \
        "chunking changed decoded tokens"
    print(f"-- budget {CHUNK_SIZE} tokens/step: worst decode inter-token "
          f"gap p95 {mono['itl_max_p95_s']*1e3:.1f} -> "
          f"{chunked['itl_max_p95_s']*1e3:.1f} ms "
          f"(token-for-token identical; "
          f"{int(chunked['prefill_chunks'])} chunks over "
          f"{int(chunked['steps'])} steps vs {int(mono['steps'])})")

    # -- trace counts: bucketing on vs off over randomized prompt lengths
    rng = np.random.default_rng(7)
    lens = rng.integers(1, 65, 40)
    for tag, growth in (("bucket-on", 2.0), ("bucket-off", 0.0)):
        before = prefill_cache_info()["chunk_misses"]
        jobs_b = [(i * CHUNK_STEP_DT, "base",
                   rng.integers(1, cfg.vocab, int(n)).tolist(), 2)
                  for i, n in enumerate(lens)]
        _run_chunk_arm(cfg, params, jobs_b, chunk=64, budget=None,
                       growth=growth, max_seq=96)
        traces = prefill_cache_info()["chunk_misses"] - before
        out[f"{tag}_traces"] = traces
        csv_row(f"serving/prefill-{tag}", traces,
                f"prompt_lens={len(set(lens.tolist()))}")
    print(f"-- {len(set(lens.tolist()))} distinct prompt lengths: "
          f"{out['bucket-on_traces']} distinct prefill traces with the "
          f"bucket ladder vs {out['bucket-off_traces']} without "
          f"(one per tail length)")
    for s in (mono, chunked):
        s.pop("_generated")
    return out


# ----------------------------------------- prefix cache (part 5)
PC_STEP_DT = 1e-3           # one simulated engine step = 1 ms
PC_TOKEN_COST = 2e-5        # + 20 µs of virtual step time per prefilled token
PC_SYS_LEN = 64             # shared system prompt (8 full pages)
PC_CONVS = 3
PC_TURNS = 3
PC_CHUNK = 16
PC_PAGE = 8


def _run_prefix_cache_arm(cfg, params, *, cache: bool):
    """Drive PC_TURNS turns of PC_CONVS conversations over one shared
    system prompt: turn k+1's prompt is turn k's prompt + generated reply
    + a fresh user message, submitted as one drive_simulated episode per
    turn on a persistent engine (the cache lives across episodes).  The
    user messages and arrival jitter come from a fixed seed, so both arms
    see the identical workload; the replies are whatever the engine
    generates — asserted identical across arms by the caller."""
    clock = VirtualClock()
    eng = ServingEngine(
        [EngineModel("base", params, cfg, kv_slots=PC_CONVS + 1,
                     max_seq=64, kv_layout="paged", page_size=PC_PAGE,
                     n_pages=128, prefix_cache=cache)],
        sched=SchedulerConfig(max_prefill_per_step=2,
                              prefill_token_budget=PC_CHUNK),
        clock=clock, prefill_chunk=PC_CHUNK)
    rng = np.random.default_rng(11)
    sys_prefix = rng.integers(1, cfg.vocab, PC_SYS_LEN).tolist()
    hist = {c: list(sys_prefix) for c in range(PC_CONVS)}
    for turn in range(PC_TURNS):
        jobs = []
        for c in range(PC_CONVS):
            arrival = clock.t + float(rng.exponential(2.0)) * PC_STEP_DT
            gen = int(rng.integers(6, 10))
            jobs.append((arrival, "base", list(hist[c]), gen))
        rid_start = eng._next_rid
        drive_simulated(
            eng, clock, jobs, dt=PC_STEP_DT,
            step_dt=lambda rec: (PC_STEP_DT
                                 + PC_TOKEN_COST * rec.prefill_tokens))
        # rids are handed out in submission (= sorted arrival) order; map
        # them back to conversations through that order — prompts alone
        # cannot disambiguate turn 0, where every conversation submits the
        # bare system prompt
        order = sorted(range(PC_CONVS), key=lambda c: jobs[c][0])
        for i, c in enumerate(order):
            req = eng.requests[rid_start + i]
            assert list(req.prompt) == hist[c], "conversation map slipped"
            hist[c] = hist[c] + list(req.generated) + rng.integers(
                1, cfg.vocab, int(rng.integers(8, 17))).tolist()
    summary = eng.summary(clock.t)
    summary["_generated"] = {rid: list(r.generated)
                            for rid, r in eng.requests.items()}
    return summary


def prefix_cache_bench() -> dict:
    print("\n== Radix-tree prefix cache "
          "(shared system prompt, multi-turn, virtual clock) ==")
    cfg = get_config("gemma-7b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    out = {}
    for cache in (False, True):
        tag = "cache-on" if cache else "cache-off"
        s = _run_prefix_cache_arm(cfg, params, cache=cache)
        out[tag] = s
        csv_row(f"serving/prefix-{tag}", s["prefill_tokens"],
                f"hit_tokens={int(s['prefix_hit_tokens'])};"
                f"ttft_p95_ms={s['ttft_p95_s']*1e3:.1f};"
                f"steps={int(s['steps'])}")
        print(f"-- {tag}:")
        print(format_summary(s))
    off, on = out["cache-off"], out["cache-on"]
    assert on["_generated"] == off["_generated"], \
        "prefix cache changed decoded tokens"
    assert on["prefill_tokens"] < off["prefill_tokens"], \
        "cache-on arm must compute strictly fewer prefill tokens"
    assert on["ttft_p95_s"] < off["ttft_p95_s"], \
        "cache-on arm must strictly drop TTFT p95"
    print(f"-- shared {PC_SYS_LEN}-token system prompt, {PC_CONVS} "
          f"conversations × {PC_TURNS} turns: computed prefill tokens "
          f"{int(off['prefill_tokens'])} -> {int(on['prefill_tokens'])} "
          f"({int(on['prefix_hit_tokens'])} served from cache, "
          f"{on['prefix_hit_rate']:.0%} hit rate); ttft p95 "
          f"{off['ttft_p95_s']*1e3:.1f} -> {on['ttft_p95_s']*1e3:.1f} ms; "
          f"{int(on['kv_prefix_cached_pages'])} cached pages resident, "
          f"{int(on['kv_prefix_evictions'])} LRU evictions "
          f"(token-for-token identical)")
    for s in out.values():
        s.pop("_generated")
    return out


# ------------------------------------- component breakdown (part 6)
def component_breakdown(trace_out: str = "") -> dict:
    """Per-step component breakdown via the structured tracer, overlap on
    vs off on the part-3 workload.  Engine on a VirtualClock (identical,
    deterministic schedules across arms), tracer on the wall clock (real
    host seconds per component)."""
    print("\n== Per-step component breakdown "
          "(structured tracer, overlap on vs off) ==")
    cfg = get_config("gemma-7b", smoke=True)
    params_a = _checkpointify(init_params(jax.random.PRNGKey(0), cfg))
    params_b = perturbed_variant(params_a)
    jobs = _overlap_workload(cfg)
    bpt = _install_tick_bytes(cfg, params_a, params_b)

    # Warmup arm populates the shared jit caches so the component tables
    # compare scheduling overhead, not XLA compile time.
    _run_overlap_arm(cfg, params_a, params_b, jobs, overlap=False,
                     bytes_per_tick=bpt)

    arms = ("overlap-off", "overlap-on")
    out = {}
    for tag in arms:
        s = _run_overlap_arm(cfg, params_a, params_b, jobs,
                             overlap=(tag == "overlap-on"),
                             bytes_per_tick=bpt, tracer=Tracer())
        s.pop("_generated")
        out[tag] = s
        total = sum(v for k, v in s.items() if k.startswith("component_"))
        csv_row(f"serving/components-{tag}", total / max(s["steps"], 1) * 1e6,
                f"total_ms={total*1e3:.1f};steps={int(s['steps'])}")

    steps = {t: max(int(out[t]["steps"]), 1) for t in arms}
    print(f"{'component':<10}" + "".join(f"{t:>24}" for t in arms))
    print(f"{'':<10}" + f"{'total ms':>14} {'us/step':>9}" * len(arms))
    for comp in TRACE_COMPONENTS:
        vals = [out[t].get(f"component_{comp}_s", 0.0) for t in arms]
        if not any(vals):
            continue
        print(f"{comp:<10}" + "".join(
            f"{v*1e3:>14.2f} {v*1e6/steps[t]:>9.1f}"
            for t, v in zip(arms, vals)))
    print(f"-- host seconds per component (wall-clock tracer, identical "
          f"virtual-clock schedule per arm): overlap turns "
          f"{int(out['overlap-off']['install_stall_steps'])} token-less "
          f"install stall steps into "
          f"{int(out['overlap-on']['install_stall_steps'])}, finishing in "
          f"{int(out['overlap-on']['steps'])} vs "
          f"{int(out['overlap-off']['steps'])} steps")

    if trace_out:
        # Deterministic artifact: same workload, engine AND tracer on one
        # VirtualClock — byte-identical across runs, Perfetto-loadable.
        clock = VirtualClock()
        tracer = Tracer(clock=clock)
        _run_overlap_arm(cfg, params_a, params_b, jobs, overlap=True,
                         bytes_per_tick=bpt, tracer=tracer, clock=clock)
        tracer.export_chrome_trace(trace_out)
        out["trace_events"] = len(tracer.events)
        print(f"-- wrote deterministic Chrome trace "
              f"({len(tracer.events)} events) to {trace_out} — load in "
              "chrome://tracing or https://ui.perfetto.dev")
    return out


# -------------------------------------- wear & write energy (part 7)
WEAR_STEP_DT = 1e-3         # one simulated engine step = 1 ms
WEAR_N_PAGES = 48
WEAR_SYS_LEN = 16           # shared system prompt (2 full pages)


def _wear_workload(cfg, seed: int = 9, n: int = 14):
    """Two-tenant Poisson arrivals in virtual time behind one shared
    system prompt: tenant switches produce weight installs (the flip
    plane), prefix-cache hits produce avoided page writes (the KV
    plane)."""
    rng = np.random.default_rng(seed)
    sys_prefix = rng.integers(1, cfg.vocab, WEAR_SYS_LEN).tolist()
    t, jobs = 0.0, []
    for i in range(n):
        t += float(rng.exponential(2.0)) * WEAR_STEP_DT
        plen = int(rng.integers(3, 10))
        prompt = sys_prefix + rng.integers(1, cfg.vocab, plen).tolist()
        jobs.append((t, "base" if i % 2 == 0 else "variant", prompt,
                     int(rng.integers(6, 12))))
    return jobs


def _run_wear_arm(cfg, params_a, params_b, jobs, *, reuse: bool):
    """One wear arm: paged KV + prefix cache on both tenants, instant
    installs on a virtual clock — the schedule is identical across reuse
    arms (reuse only changes install accounting, decode runs on the
    full-precision params), so the energy comparison is apples to
    apples.  Returns (engine, summary) — the caller reads the wear map
    off the engine."""
    clock = VirtualClock()
    kv = dict(kv_slots=4, max_seq=64, kv_layout="paged",
              page_size=PAGE_SIZE, n_pages=WEAR_N_PAGES, prefix_cache=True)
    eng = ServingEngine(
        [EngineModel("base", params_a, cfg, **kv),
         EngineModel("variant", params_b, cfg, **kv)],
        weight_arena_slots=cfg.n_layers + 1,   # forces tenant swaps
        reuse=reuse,
        sched=SchedulerConfig(max_prefill_per_step=4,
                              model_turn_steps=TURN_STEPS),
        clock=clock)
    summary = drive_simulated(eng, clock, jobs, dt=WEAR_STEP_DT)
    summary["_generated"] = {r.rid: list(r.generated)
                             for r in eng.requests.values()}
    return eng, summary


def wear_energy_bench(wear_json: str = "") -> dict:
    print("\n== Wear & write energy "
          "(reuse on vs off, virtual clock, 2 tenants, paged KV) ==")
    cfg = get_config("gemma-7b", smoke=True)
    params_a = _checkpointify(init_params(jax.random.PRNGKey(0), cfg))
    params_b = perturbed_variant(params_a)
    jobs = _wear_workload(cfg)

    out = {}
    engines = {}
    for reuse in (False, True):
        tag = "reuse-on" if reuse else "reuse-off"
        eng, s = _run_wear_arm(cfg, params_a, params_b, jobs, reuse=reuse)
        engines[tag] = eng
        out[tag] = s
        csv_row(f"serving/wear-{tag}", s["install_energy_j"] * 1e6,
                f"flips={int(s['install_cell_flips'])};"
                f"pulses={int(s['install_write_pulses'])};"
                f"kv_writes={int(s['kv_page_writes'])}")
        print(f"-- {tag}:")
        print(format_summary(s))
    off, on = out["reuse-off"], out["reuse-on"]
    assert on["_generated"] == off["_generated"], \
        "reuse changed decoded tokens"
    assert on["steps"] == off["steps"], "reuse changed the schedule"
    assert on["install_energy_j"] < off["install_energy_j"], \
        "§V-C equal-skip install must spend strictly less write energy"
    print(f"-- same schedule ({int(on['steps'])} steps, token-for-token "
          f"identical): install write energy "
          f"{off['install_energy_j']*1e3:.3f} -> "
          f"{on['install_energy_j']*1e3:.3f} mJ "
          f"({1 - on['install_energy_j']/off['install_energy_j']:.1%} "
          f"saved by §V-C equal-skip), KV page writes "
          f"{int(on['kv_page_writes'])} "
          f"({int(on['kv_page_writes_avoided'])} avoided via shared "
          f"prefixes, {on['kv_write_energy_j']*1e3:.3f} mJ); wear gini "
          f"weight {on['wear_gini_weight']:.3f}, kv {on['wear_gini_kv']:.3f}")
    if wear_json:
        with open(wear_json, "w") as f:
            json.dump(engines["reuse-on"].wear.as_json(), f, indent=2,
                      sort_keys=True)
            f.write("\n")
        print(f"-- wrote reuse-on wear map to {wear_json}")
    for s in out.values():
        s.pop("_generated")
    return out


# --------------------- wear-aware placement & fault sweep (part 8)
FAULT_SWEEP_RATES = (0.005, 0.01, 0.02)
# seed chosen so the 2% arm faults at least one weight slot and one KV
# page on this workload (the 0/0.5/1% arms may legitimately stay clean)
FAULT_SEED = 100


def _run_fault_arm(cfg, params_a, params_b, jobs, *, wear_aware=0.0,
                   fault_rate=0.0, fault_seed=0, spare_slots=1):
    """One placement/fault arm over the part-7 workload shape: paged KV
    + prefix cache, instant installs on a virtual clock.  Neither knob
    may move the decoded tokens — wear-aware placement only re-ranks
    eviction victims and free pages (installs are bookkeeping; decode
    runs on full-precision params), and a surviving fault remaps the
    write to a healthy unit with identical contents.

    `spare_slots=1` forces tenant swaps (the wear arms need traffic to
    steer); the fault arms run with 3 — room for both tenants to decode
    concurrently plus one retirement — so a weight-slot fault remaps to
    a healthy slot instead of exhausting the arena.  Endurance headroom
    is a provisioning decision: a stuck-at slot is capacity permanently
    gone."""
    clock = VirtualClock()
    kv = dict(kv_slots=4, max_seq=64, kv_layout="paged",
              page_size=PAGE_SIZE, n_pages=WEAR_N_PAGES, prefix_cache=True)
    eng = ServingEngine(
        [EngineModel("base", params_a, cfg, **kv),
         EngineModel("variant", params_b, cfg, **kv)],
        weight_arena_slots=cfg.n_layers + spare_slots,
        reuse=True,
        sched=SchedulerConfig(max_prefill_per_step=4,
                              model_turn_steps=TURN_STEPS),
        clock=clock, wear_aware=wear_aware,
        fault_rate=fault_rate, fault_seed=fault_seed)
    summary = drive_simulated(eng, clock, jobs, dt=WEAR_STEP_DT)
    summary["_generated"] = {r.rid: list(r.generated)
                             for r in eng.requests.values()}
    return eng, summary


def fault_wear_bench() -> dict:
    print("\n== Wear-aware placement & stuck-at fault sweep "
          "(virtual clock, 2 tenants, paged KV) ==")
    cfg = get_config("gemma-7b", smoke=True)
    params_a = _checkpointify(init_params(jax.random.PRNGKey(0), cfg))
    params_b = perturbed_variant(params_a)
    jobs = _wear_workload(cfg)

    out = {}
    # -- wear-aware placement: identical schedule, flatter write spread
    for weight in (0.0, 1.0):
        tag = "wear-on" if weight else "wear-off"
        _, s = _run_fault_arm(cfg, params_a, params_b, jobs,
                              wear_aware=weight)
        out[tag] = s
        csv_row(f"serving/faults-{tag}", s["wear_gini_weight"],
                f"gini_kv={s['wear_gini_kv']:.3f};"
                f"flips={int(s['install_cell_flips'])};"
                f"installs={int(s['installs'])}")
        print(f"-- {tag}:")
        print(format_summary(s))
    off, on = out["wear-off"], out["wear-on"]
    assert on["_generated"] == off["_generated"], \
        "wear-aware placement changed decoded tokens"
    assert on["steps"] == off["steps"], \
        "wear-aware placement changed the schedule"
    assert on["wear_gini_weight"] < off["wear_gini_weight"], \
        "wear blend must strictly flatten the weight plane's write spread"
    print(f"-- same schedule ({int(on['steps'])} steps, token-for-token "
          f"identical): weight-plane wear gini "
          f"{off['wear_gini_weight']:.3f} -> {on['wear_gini_weight']:.3f} "
          f"with the wear-aware victim/free-page blend on")

    # -- fault sweep 0 -> 2%: token-equivalent with survivals logged.
    # The sweep's own rate-0 arm is the baseline (same arena shape).
    for rate in (0.0,) + FAULT_SWEEP_RATES:
        tag = f"fault-{rate:g}"
        _, s = _run_fault_arm(cfg, params_a, params_b, jobs,
                              fault_rate=rate, fault_seed=FAULT_SEED,
                              spare_slots=3)
        out[tag] = s
        assert s["requests_finished"] == len(jobs), \
            f"rate {rate:g}: a request never finished"
        assert s["_generated"] == out["fault-0"]["_generated"], \
            f"rate {rate:g}: a surviving fault changed decoded tokens"
        assert s["faults_survived"] == \
            s["slots_retired"] + s["pages_retired"]
        csv_row(f"serving/{tag}", s["faults_survived"],
                f"slots_retired={int(s['slots_retired'])};"
                f"pages_retired={int(s['pages_retired'])}")
    assert out["fault-0"]["faults_survived"] == 0
    top = out[f"fault-{FAULT_SWEEP_RATES[-1]:g}"]
    assert top["faults_survived"] > 0, \
        "sweep never injected a fault — seed/rate too conservative"
    print(f"-- fault sweep 0 -> {FAULT_SWEEP_RATES[-1]:.1%} token-"
          f"equivalent: " + ", ".join(
              f"{r:.1%}: {int(out[f'fault-{r:g}']['faults_survived'])} "
              f"survived ({int(out[f'fault-{r:g}']['slots_retired'])} "
              f"slots, {int(out[f'fault-{r:g}']['pages_retired'])} pages "
              f"retired)" for r in FAULT_SWEEP_RATES))
    for s in out.values():
        s.pop("_generated")
    return out


# ------------------- kernel backend & fused sampling (part 9)
KB_STEP_DT = 1e-3           # one simulated engine step = 1 ms
KB_PAGE = 4
KB_N_PAGES = 64
KB_SYS_LEN = 8              # shared system prompt (2 full pages)


def _kernel_workload(cfg, seed: int = 13, n: int = 12):
    """One-tenant Poisson arrivals behind a shared system prompt, a third
    of them sampled (fixed seed or rid-derived key) so the fused sampler
    sees greedy, top-k, and plain-temperature rows in the same batch.
    Jobs carry per-request sampling kwargs, so this part drives its own
    arrival loop instead of `drive_simulated`."""
    rng = np.random.default_rng(seed)
    sys_prefix = rng.integers(1, cfg.vocab, KB_SYS_LEN).tolist()
    t, jobs = 0.0, []
    for i in range(n):
        t += float(rng.exponential(2.0)) * KB_STEP_DT
        plen = int(rng.integers(3, 10))
        prompt = sys_prefix + rng.integers(1, cfg.vocab, plen).tolist()
        if i % 3 == 1:
            kw = dict(temperature=0.8, top_k=9, seed=100 + i)
        elif i % 3 == 2:
            kw = dict(temperature=1.1)       # key derives from the rid
        else:
            kw = {}
        jobs.append((t, "base", prompt, int(rng.integers(6, 12)), kw))
    return jobs


def _run_kernel_arm(cfg, params, jobs, *, backend: str, fuse: bool):
    clock = VirtualClock()
    eng = ServingEngine(
        [EngineModel("base", params, cfg, kv_slots=4, max_seq=48,
                     kv_layout="paged", page_size=KB_PAGE,
                     n_pages=KB_N_PAGES, prefix_cache=True,
                     kernel_backend=backend)],
        sched=SchedulerConfig(max_prefill_per_step=2),
        clock=clock, tracer=Tracer(),
        fuse_sampling=fuse)
    pending = sorted((t, i) for i, (t, *_rest) in enumerate(jobs))
    for _ in range(100_000):
        if not pending and not eng.has_work():
            break
        while pending and pending[0][0] <= clock.t:
            _, i = pending.pop(0)
            _, model, prompt, gen, kw = jobs[i]
            eng.submit(model, prompt, max_new_tokens=gen, **kw)
        if eng.has_work():
            eng.step()
        clock.advance(KB_STEP_DT)
    else:
        raise RuntimeError("part-9 arm did not drain — engine livelock?")
    summary = eng.summary(clock.t)
    summary["_generated"] = {r.rid: list(r.generated)
                             for r in eng.requests.values()}
    summary["sample_syncs_max"] = max(
        (rec.sample_syncs for rec in eng.metrics.steps if rec.n_decoded),
        default=0)
    return summary


def kernel_backend_bench() -> dict:
    print("\n== Kernel backend & fused sampling "
          "(virtual clock, XLA vs Pallas-interpret, split vs fused) ==")
    cfg = get_config("gemma-7b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    jobs = _kernel_workload(cfg)

    arms = {"xla-split": ("xla", False),
            "xla-fused": ("xla", True),
            "pallas-fused": ("pallas", True)}
    out = {}
    for tag, (backend, fuse) in arms.items():
        _run_kernel_arm(cfg, params, jobs, backend=backend,
                        fuse=fuse)                          # jit warmup
        s = _run_kernel_arm(cfg, params, jobs, backend=backend, fuse=fuse)
        out[tag] = s
        csv_row(f"serving/kernel-{tag}",
                s.get("component_decode_s", 0.0) * 1e3,
                f"sample_ms={s.get('component_sample_s', 0.0)*1e3:.2f};"
                f"syncs_max={int(s['sample_syncs_max'])};"
                f"steps={int(s['steps'])}")
        print(f"-- {tag}:")
        print(format_summary(s))

    base = out["xla-split"]
    assert out["xla-fused"]["_generated"] == base["_generated"], \
        "fused sampling changed decoded tokens"
    assert out["pallas-fused"]["_generated"] == base["_generated"], \
        "pallas kernel backend changed decoded tokens"
    for tag, s in out.items():
        assert s["steps"] == base["steps"], f"{tag} changed the schedule"
        assert s["sample_syncs_max"] <= 1, \
            f"{tag}: sampling cost more than one host sync per step"
        assert s.get("component_sample_s", 0.0) > 0.0, \
            f"{tag}: tracer recorded no sample spans"
    out["tokens_identical_fused"] = 1
    out["tokens_identical_pallas"] = 1

    tags = list(arms)
    steps = {t: max(int(out[t]["steps"]), 1) for t in tags}
    print(f"{'component':<10}" + "".join(f"{t:>24}" for t in tags))
    print(f"{'':<10}" + f"{'total ms':>14} {'us/step':>9}" * len(tags))
    for comp in TRACE_COMPONENTS:
        vals = [out[t].get(f"component_{comp}_s", 0.0) for t in tags]
        if not any(vals):
            continue
        print(f"{comp:<10}" + "".join(
            f"{v*1e3:>14.2f} {v*1e6/steps[t]:>9.1f}"
            for t, v in zip(tags, vals)))
    print(f"-- token-for-token identical across all three arms over "
          f"{int(base['steps'])} steps; sampling host syncs per decoded "
          f"step: " + ", ".join(
              f"{t}={int(out[t]['sample_syncs_max'])}" for t in tags) +
          " (the legacy path paid one sync per row)")
    for tag in arms:
        out[tag].pop("_generated")
    return out


# ----------------------- live telemetry plane overhead (part 10)
def _run_telemetry_arm(cfg, params_a, params_b, jobs, *,
                       telemetry: bool, out_dir: str):
    """One telemetry arm over the part-7 workload shape.  The telemetry
    arm turns EVERYTHING on — windowed percentiles, an (intentionally
    breaching) SLO tracker, the JSONL event stream, the flight recorder,
    and the step watchdog — the off arm is the stock engine.  Same
    virtual-clock schedule both ways, so the decoded tokens must match
    bit for bit; the wall `time.perf_counter` around the drive is the
    honest host cost (the engine's own wall_s is virtual here)."""
    clock = VirtualClock()
    kv = dict(kv_slots=4, max_seq=64, kv_layout="paged",
              page_size=PAGE_SIZE, n_pages=WEAR_N_PAGES, prefix_cache=True)
    kwargs = {}
    if telemetry:
        # ITL target of half a step: guaranteed to burn, so the bench
        # exercises breach -> trace instant -> flight dump every run
        kwargs = dict(
            telemetry=TelemetryConfig(
                window=64,
                slo=SLOConfig(itl_p95_s=WEAR_STEP_DT / 2),
                events_path=os.path.join(out_dir, "events.jsonl")),
            recorder=FlightRecorder(64, out_dir=out_dir),
            stall_timeout_s=300.0)
    eng = ServingEngine(
        [EngineModel("base", params_a, cfg, **kv),
         EngineModel("variant", params_b, cfg, **kv)],
        weight_arena_slots=cfg.n_layers + 1,
        sched=SchedulerConfig(max_prefill_per_step=4,
                              model_turn_steps=TURN_STEPS),
        clock=clock, **kwargs)
    t0 = time.perf_counter()
    summary = drive_simulated(eng, clock, jobs, dt=WEAR_STEP_DT)
    host_s = time.perf_counter() - t0
    summary["_generated"] = {r.rid: list(r.generated)
                             for r in eng.requests.values()}
    return eng, summary, host_s


def telemetry_bench(telemetry_dir: str = "") -> dict:
    print("\n== Live telemetry plane "
          "(off vs windows+SLO+recorder+watchdog, identical schedule) ==")
    import tempfile

    cfg = get_config("gemma-7b", smoke=True)
    params_a = _checkpointify(init_params(jax.random.PRNGKey(0), cfg))
    params_b = perturbed_variant(params_a)
    jobs = _wear_workload(cfg)
    out_dir = telemetry_dir or tempfile.mkdtemp(prefix="telemetry-bench-")
    os.makedirs(out_dir, exist_ok=True)

    # warmup arm: pay the jit compiles outside the timed comparison
    _run_telemetry_arm(cfg, params_a, params_b, jobs, telemetry=False,
                       out_dir=out_dir)
    eng_off, off, host_off = _run_telemetry_arm(
        cfg, params_a, params_b, jobs, telemetry=False, out_dir=out_dir)
    eng_on, on, host_on = _run_telemetry_arm(
        cfg, params_a, params_b, jobs, telemetry=True, out_dir=out_dir)

    assert on["_generated"] == off["_generated"], \
        "telemetry changed decoded tokens"
    assert on["steps"] == off["steps"], "telemetry changed the schedule"
    steps = int(on["steps"])
    overhead_us = max(host_on - host_off, 0.0) / max(steps, 1) * 1e6
    # the ratio is what the regression gate watches: on this class of
    # host the absolute delta is noise-dominated (and can clamp to 0,
    # which would make a relative-tolerance gate a zero ceiling), while
    # on/off is always positive and ~1 unless a hook lands on the
    # decode path
    overhead_ratio = host_on / max(host_off, 1e-9)

    # the artifacts the on arm produced, validated in-process
    prom = prometheus_text(eng_on.metrics.registry, eng_on.telemetry)
    prom_errors = validate_prometheus_text(prom)
    assert not prom_errors, f"invalid Prometheus exposition: {prom_errors}"
    events_path = os.path.join(out_dir, "events.jsonl")
    eng_on.telemetry.close()
    with open(events_path, encoding="utf-8") as f:
        events_text = f.read()
    events_errors = validate_events_jsonl(events_text)
    assert not events_errors, f"invalid events JSONL: {events_errors}"
    events_lines = len(events_text.splitlines())
    health = eng_on.health()
    assert health["ok"] is False, \
        "the intentionally-tight ITL SLO must be breached"
    assert eng_on.recorder.dumps, "SLO breach must leave a flight dump"

    for tag, s, host_s in (("telemetry-off", off, host_off),
                           ("telemetry-on", on, host_on)):
        csv_row(f"serving/{tag}", host_s / max(steps, 1) * 1e6,
                f"steps={steps}")
        print(f"-- {tag}: host {host_s*1e3:.1f} ms over {steps} steps "
              f"({host_s/max(steps,1)*1e6:.0f} us/step)")
    print(format_summary(on))
    print(f"-- token-for-token identical over {steps} steps; telemetry "
          f"host overhead {overhead_us:.0f} us/step; "
          f"{events_lines} JSONL events, "
          f"{len(eng_on.recorder.dumps)} flight dump(s) "
          f"({', '.join(os.path.basename(p) for p in eng_on.recorder.dumps)}), "
          f"prom exposition {len(prom.splitlines())} lines (valid)")
    if telemetry_dir:
        with open(os.path.join(out_dir, "prom.txt"), "w") as f:
            f.write(prom)
        with open(os.path.join(out_dir, "health.json"), "w") as f:
            json.dump(_json_safe(health), f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"-- wrote prom.txt / health.json / events.jsonl / flight "
              f"dumps to {out_dir}")
    for s in (off, on):
        s.pop("_generated")
    return {
        "telemetry-off": off, "telemetry-on": on,
        "host_s_off": host_off, "host_s_on": host_on,
        "overhead_us_per_step": overhead_us,
        "host_overhead_ratio": overhead_ratio,
        "tokens_identical": 1.0,
        "events_lines": float(events_lines),
        "flight_dumps": float(len(eng_on.recorder.dumps)),
    }


# ------------------------------------------------- headline persistence
_DEFAULT_OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serving.json")


def _json_safe(obj):
    """NaN/inf -> None recursively, so the dump is strict JSON."""
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, float) and not np.isfinite(obj):
        return None
    return obj


def _headlines(results: dict) -> dict:
    """Compress each part's summaries to its headline numbers."""
    h = {}
    t = results.get("tenants")
    if t:
        h["tenants"] = {
            "latency_p50_s": t["reuse-on"]["latency_p50_s"],
            "latency_p95_s": t["reuse-on"]["latency_p95_s"],
            "tokens_per_s": t["reuse-on"]["tokens_per_s"],
            "wire_saved_frac": t["wire_saved_frac"],
        }
    lay = results.get("layout")
    if lay:
        h["layout"] = {
            "slot_max_concurrent": lay["slot"]["max_concurrent"],
            "paged_max_concurrent": lay["paged"]["max_concurrent"],
            "paged_pages_saved": lay["paged"]["kv_pages_saved"],
            "slot_latency_p50_s": lay["slot"]["latency_p50_s"],
            "paged_latency_p50_s": lay["paged"]["latency_p50_s"],
        }
    ov = results.get("overlap")
    if ov:
        h["overlap"] = {
            "stall_steps_sync": ov["overlap-off"]["install_stall_steps"],
            "stall_steps_overlap": ov["overlap-on"]["install_stall_steps"],
            "itl_max_p95_s_sync": ov["overlap-off"]["itl_max_p95_s"],
            "itl_max_p95_s_overlap": ov["overlap-on"]["itl_max_p95_s"],
            "ttft_p95_s_overlap": ov["overlap-on"]["ttft_p95_s"],
            "hidden_bytes": ov["overlap-on"]["overlap_hidden_bytes"],
        }
    ch = results.get("chunked")
    if ch:
        h["chunked"] = {
            "itl_max_p95_s_mono": ch["chunk-off"]["itl_max_p95_s"],
            "itl_max_p95_s_chunked": ch["chunk-on"]["itl_max_p95_s"],
            "ttft_p95_s_chunked": ch["chunk-on"]["ttft_p95_s"],
            "traces_bucket_on": ch["bucket-on_traces"],
            "traces_bucket_off": ch["bucket-off_traces"],
        }
    pc = results.get("prefix_cache")
    if pc:
        h["prefix_cache"] = {
            "prefill_tokens_off": pc["cache-off"]["prefill_tokens"],
            "prefill_tokens_on": pc["cache-on"]["prefill_tokens"],
            "prefix_hit_rate": pc["cache-on"]["prefix_hit_rate"],
            "ttft_p95_s_off": pc["cache-off"]["ttft_p95_s"],
            "ttft_p95_s_on": pc["cache-on"]["ttft_p95_s"],
        }
    w = results.get("wear")
    if w:
        h["wear"] = {
            "install_energy_j_off": w["reuse-off"]["install_energy_j"],
            "install_energy_j_on": w["reuse-on"]["install_energy_j"],
            "install_cell_flips_on": w["reuse-on"]["install_cell_flips"],
            "kv_write_energy_j": w["reuse-on"]["kv_write_energy_j"],
            "kv_page_writes": w["reuse-on"]["kv_page_writes"],
            "kv_page_writes_avoided":
                w["reuse-on"]["kv_page_writes_avoided"],
            "wear_gini_weight": w["reuse-on"]["wear_gini_weight"],
            "wear_gini_kv": w["reuse-on"]["wear_gini_kv"],
        }
    fl = results.get("faults")
    if fl:
        top = fl[f"fault-{FAULT_SWEEP_RATES[-1]:g}"]
        h["faults"] = {
            "wear_gini_weight_off": fl["wear-off"]["wear_gini_weight"],
            "wear_gini_weight_on": fl["wear-on"]["wear_gini_weight"],
            "faults_survived": top["faults_survived"],
            "slots_retired": top["slots_retired"],
            "pages_retired": top["pages_retired"],
            "steps": fl["wear-on"]["steps"],
        }
    kb = results.get("kernel")
    if kb:
        h["kernel"] = {
            "tokens_identical_fused": kb["tokens_identical_fused"],
            "tokens_identical_pallas": kb["tokens_identical_pallas"],
            "sample_syncs_max_split": kb["xla-split"]["sample_syncs_max"],
            "sample_syncs_max_fused": kb["xla-fused"]["sample_syncs_max"],
            "sample_syncs_max_pallas": kb["pallas-fused"]["sample_syncs_max"],
            "steps": kb["pallas-fused"]["steps"],
        }
        # wall-clock component seconds per arm: reported, never gated
        for tag in ("xla-split", "xla-fused", "pallas-fused"):
            h["kernel"][f"decode_s_{tag}"] = \
                kb[tag].get("component_decode_s", 0.0)
            h["kernel"][f"sample_s_{tag}"] = \
                kb[tag].get("component_sample_s", 0.0)
    tel = results.get("telemetry")
    if tel:
        h["telemetry"] = {
            # the identity bit and schedule length are deterministic and
            # gated at tolerance 0; the host overhead ratio is wall-clock
            # and gated only as a generous ceiling (us/step is reported
            # but ungated: the delta is noise on shared CI hosts)
            "tokens_identical": tel["tokens_identical"],
            "steps": tel["telemetry-on"]["steps"],
            "overhead_us_per_step": tel["overhead_us_per_step"],
            "host_overhead_ratio": tel["host_overhead_ratio"],
            "events_lines": tel["events_lines"],
            "flight_dumps": tel["flight_dumps"],
            "ttft_p95_s": tel["telemetry-on"]["ttft_p95_s"],
            "itl_max_p95_s": tel["telemetry-on"]["itl_max_p95_s"],
        }
    comp = results.get("components")
    if comp:
        h["components"] = {
            tag: {k: v for k, v in comp[tag].items()
                  if k.startswith("component_")}
            for tag in ("overlap-off", "overlap-on") if tag in comp}
        if "trace_events" in comp:
            h["components"]["trace_events"] = comp["trace_events"]
    return h


def _write_bench_json(path: str, headlines: dict) -> None:
    doc = {"bench": "serving", "arch": "gemma-7b(smoke)",
           "parts": headlines}
    with open(path, "w") as f:
        json.dump(_json_safe(doc), f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"\nwrote per-part headline numbers to {path}")


def tenant_reuse_bench() -> dict:
    print("\n== Continuous-batching serving engine (Poisson, 2 tenants) ==")
    cfg = get_config("gemma-7b", smoke=True)
    # _checkpointify injects the asymmetric outlier tails real checkpoints
    # have (random inits quantize already-centered, hiding §V-C).
    params_a = _checkpointify(init_params(jax.random.PRNGKey(0), cfg))
    params_b = perturbed_variant(params_a)
    jobs = _workload()

    # Warmup arm over the full workload populates the shared jit caches
    # (every prompt length) so timed arms compare scheduling, not XLA.
    _run_arm(cfg, params_a, params_b, jobs, reuse=True)

    out = {}
    for reuse in (False, True):
        tag = "reuse-on" if reuse else "reuse-off"
        s = _run_arm(cfg, params_a, params_b, jobs, reuse=reuse)
        out[tag] = s
        csv_row(f"serving/{tag}", s["latency_p50_s"] * 1e6,
                f"p95_us={s['latency_p95_s']*1e6:.0f};"
                f"tok_s={s['tokens_per_s']:.1f};"
                f"wire_mb={s['install_wire_bytes']/1e6:.3f};"
                f"installs={int(s['installs'])}")
        print(f"-- {tag}:")
        print(format_summary(s))
    # Install counts are wall-clock dependent (Poisson arrivals vs real
    # turn boundaries), so compare wire bytes per byte of installed
    # weights, not absolute MB across arms.
    saved = out["reuse-on"]["install_savings"]
    print(f"-- cross-tenant §V-C reuse ships {saved:.1%} fewer wire bytes "
          f"per installed weight byte (reuse-off ships raw by definition); "
          f"absolute: {out['reuse-off']['install_wire_bytes']/1e6:.2f} MB "
          f"over {int(out['reuse-off']['installs'])} installs vs "
          f"{out['reuse-on']['install_wire_bytes']/1e6:.2f} MB over "
          f"{int(out['reuse-on']['installs'])}")
    out["wire_saved_frac"] = saved
    return out


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description="serving-engine benchmarks")
    p.add_argument("--parts", default="1,2,3,4,5,6,7,8,9,10",
                   help="comma-separated parts to run: 1 tenant reuse, "
                        "2 paged-vs-slot, 3 install overlap, 4 chunked "
                        "prefill, 5 prefix cache, 6 component breakdown, "
                        "7 wear & write energy, 8 wear-aware placement "
                        "& fault sweep, 9 kernel backend & fused "
                        "sampling, 10 live telemetry plane overhead")
    p.add_argument("--out", default=_DEFAULT_OUT,
                   help="path for the BENCH_serving.json headline dump "
                        "('' disables)")
    p.add_argument("--trace-out", default="",
                   help="part 6: also write the deterministic virtual-clock "
                        "Chrome trace to this path")
    p.add_argument("--wear-json", default="",
                   help="part 7: also write the reuse-on arm's per-plane "
                        "wear map (writes/flips/pulses per slot and page) "
                        "to this path")
    p.add_argument("--telemetry-dir", default="",
                   help="part 10: keep the telemetry-on arm's artifacts "
                        "(events.jsonl, prom.txt, health.json, flight "
                        "dumps) in this directory instead of a tempdir")
    args = p.parse_args(argv)
    setup_compile_cache()
    parts = sorted({int(x) for x in args.parts.split(",") if x.strip()})

    results = {}
    if 1 in parts:
        results["tenants"] = tenant_reuse_bench()
    if 2 in parts:
        results["layout"] = paged_vs_slot()
    if 3 in parts:
        results["overlap"] = overlap_vs_sync()
    if 4 in parts:
        results["chunked"] = chunked_prefill_bench()
    if 5 in parts:
        results["prefix_cache"] = prefix_cache_bench()
    if 6 in parts:
        results["components"] = component_breakdown(args.trace_out)
    if 7 in parts:
        results["wear"] = wear_energy_bench(args.wear_json)
    if 8 in parts:
        results["faults"] = fault_wear_bench()
    if 9 in parts:
        results["kernel"] = kernel_backend_bench()
    if 10 in parts:
        results["telemetry"] = telemetry_bench(args.telemetry_dir)
    if args.out:
        _write_bench_json(args.out, _headlines(results))
    return results


if __name__ == "__main__":
    main()
