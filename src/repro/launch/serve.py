"""Serving launcher: batched prefill + decode, the ARAS streaming executor
(weights larger than the device arena), or the continuous-batching engine
(many concurrent requests across multiple tenant models).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-7b --smoke \
        --batch 4 --prompt-len 32 --gen 16
    PYTHONPATH=src python -m repro.launch.serve --arch minicpm-2b --smoke \
        --streaming --arena-slots 3
    PYTHONPATH=src python -m repro.launch.serve --smoke --engine
"""
from __future__ import annotations

import argparse
import atexit
import json
import signal
import sys
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, supported_shapes
from repro.data.pipeline import DataConfig, make_batch
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.steps import cached_prefill_step, cached_serve_step
from repro.nn.model import init_params, init_serving_params


def _json_safe(obj):
    """NaN/inf -> None recursively, so metrics dumps are strict JSON."""
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, float) and not np.isfinite(obj):
        return None
    return obj


def build_engine(args, clock: Callable[[], float] = time.perf_counter):
    """The continuous-batching engine `--engine` serves: two tenants on one
    device budget, tenant-b a perturbed variant of tenant-a (the co-hosted
    fine-tune regime where cross-tenant §V-C delta installs have real
    structure), their matmul weights bf16 on the device, and every engine
    knob taken from `args` (this module's parser)."""
    from repro.serving import (EngineModel, FlightRecorder, InstallCostModel,
                               SchedulerConfig, ServingEngine, SLOConfig,
                               TelemetryConfig, Tracer)
    from repro.serving.variants import perturbed_variant

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.prefix_cache and args.kv_layout != "paged":
        raise SystemExit("--prefix-cache needs --kv-layout paged "
                         "(slot arenas have no pages to retain)")
    if args.kernel_backend == "pallas" and args.kv_layout != "paged":
        raise SystemExit("--kernel-backend pallas needs --kv-layout paged "
                         "(the kernel reads a page pool)")
    max_seq = args.prompt_len + args.gen + 8
    base = init_serving_params(jax.random.PRNGKey(0), cfg)
    variant = perturbed_variant(base)
    kv = dict(kv_slots=args.kv_slots, max_seq=max_seq,
              kv_layout=args.kv_layout, page_size=args.page_size,
              prefix_cache=args.prefix_cache,
              prefix_cache_pages=args.prefix_cache_pages,
              kernel_backend=args.kernel_backend)
    tenants = [
        EngineModel("tenant-a", base, cfg, **kv),
        EngineModel("tenant-b", variant, cfg, **kv),
    ]
    # A weight arena smaller than both tenants' layer sets forces ARAS-style
    # cross-tenant delta installs when the scheduler switches models.
    weight_slots = (args.weight_slots if args.weight_slots
                    else cfg.n_layers + 1)
    # Structured tracing costs nothing unless asked for: a Tracer on the
    # engine's clock feeds both the Chrome-trace export and the per-step
    # component_s breakdown in the summary.
    tracer = Tracer(clock=clock) if args.trace_out else None

    # Live telemetry plane: declared SLO targets + windowed percentiles
    # (constructing a TelemetryConfig turns the plane on — any exporter
    # or SLO flag implies it), plus the bounded flight recorder dumped
    # on retirement / SLO breach / stall / SIGUSR1 / crash.
    slo = None
    if args.slo_ttft_p95 or args.slo_itl_p95 or args.slo_queue_wait_p95:
        slo = SLOConfig(ttft_p95_s=args.slo_ttft_p95,
                        itl_p95_s=args.slo_itl_p95,
                        queue_wait_p95_s=args.slo_queue_wait_p95)
    telemetry = None
    if (slo is not None or args.events_out or args.prom_out
            or args.prom_port):
        telemetry = TelemetryConfig(window=args.telemetry_window, slo=slo,
                                    events_path=args.events_out)
    recorder = (FlightRecorder(args.flight_recorder_steps,
                               out_dir=args.flight_dir)
                if args.flight_recorder_steps else None)
    return ServingEngine(
        tenants, weight_arena_slots=weight_slots, tracer=tracer,
        clock=clock, telemetry=telemetry, recorder=recorder,
        stall_timeout_s=args.stall_timeout_s,
        sched=SchedulerConfig(max_prefill_per_step=4,
                              model_turn_steps=args.turn_steps,
                              policy=args.queue_policy,
                              prefill_token_budget=(
                                  args.prefill_token_budget or None)),
        install_ticks_per_step=args.install_ticks_per_step,
        overlap_installs=args.overlap_installs,
        install_cost=InstallCostModel(
            bytes_per_tick=args.install_bytes_per_tick),
        prefill_chunk=args.prefill_chunk,
        bucket_growth=args.bucket_growth,
        staging_growth=args.staging_growth,
        fuse_sampling=not args.no_fuse_sampling,
        wear_aware=args.wear_aware,
        fault_rate=args.fault_rate,
        fault_seed=args.fault_seed)


def _run_engine(args) -> None:
    """Continuous batching across ≥ 2 tenants on one device budget."""
    from repro.serving import (PromEndpoint, VirtualClock, drive_simulated,
                               format_summary, prometheus_text)

    # --virtual-clock swaps the wall clock for a VirtualClock and drives
    # arrivals in simulated time, so every artifact (trace, health, flight
    # dumps, events) is byte-deterministic — the CI telemetry-validation
    # mode.
    clock = VirtualClock() if args.virtual_clock else time.perf_counter
    eng = build_engine(args, clock)
    cfg = next(iter(eng.models.values())).cfg
    tenants = list(eng.models)
    tracer, recorder = eng.tracer, eng.recorder
    weight_slots = eng.residency.arena_slots
    if recorder is not None:
        # live-incident hooks: kill -USR1 <pid> snapshots the ring of a
        # running replica; an unhandled crash dumps it on the way down
        recorder.install_signal_handler()
        recorder.install_excepthook()
    endpoint = None
    if args.prom_port:
        endpoint = PromEndpoint(
            args.prom_port,
            lambda: prometheus_text(eng.metrics.registry, eng.telemetry))
        print("prometheus endpoint on "
              f"http://127.0.0.1:{endpoint.port}/metrics")

    # Artifact flush runs exactly once, whether the run completes, the
    # user hits Ctrl-C (KeyboardInterrupt unwinds to interpreter exit →
    # atexit), or the process is SIGTERMed (handler turns it into a normal
    # exit so atexit still fires) — a half-hour serving run killed early
    # still leaves its trace, metrics, and wear map on disk.
    done = {"flushed": False}

    def flush() -> None:
        if done["flushed"]:
            return
        done["flushed"] = True
        if args.trace_out:
            tracer.export_chrome_trace(args.trace_out)
            print(f"wrote Chrome trace ({len(tracer.events)} events) to "
                  f"{args.trace_out} — load in chrome://tracing or "
                  "https://ui.perfetto.dev")
        if args.metrics_json:
            doc = {"summary": _json_safe(eng.summary()),
                   "metrics": _json_safe(eng.metrics.registry.as_dict())}
            with open(args.metrics_json, "w") as f:
                json.dump(doc, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"wrote metrics registry + summary to {args.metrics_json}")
        if args.wear_json:
            with open(args.wear_json, "w") as f:
                json.dump(eng.wear.as_json(), f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"wrote wear map ({len(eng.wear.planes)} planes) to "
                  f"{args.wear_json}")
        if args.prom_out:
            with open(args.prom_out, "w") as f:
                f.write(prometheus_text(eng.metrics.registry, eng.telemetry))
            print(f"wrote Prometheus text exposition to {args.prom_out}")
        if eng.telemetry is not None:
            eng.telemetry.close()
        if recorder is not None and recorder.dumps:
            print(f"flight recorder wrote {len(recorder.dumps)} dump(s): "
                  + ", ".join(recorder.dumps))

    if (args.trace_out or args.metrics_json or args.wear_json
            or args.prom_out or args.events_out):
        atexit.register(flush)
        signal.signal(signal.SIGTERM, lambda signum, frame: sys.exit(1))

    rng = np.random.default_rng(0)
    if args.virtual_clock:
        # deterministic Poisson-ish arrivals in simulated time (mean gap
        # 4 ms, step dt 2 ms): the whole run — tokens, health, dumps,
        # events — is byte-reproducible, no device clock involved
        t, vjobs = 0.0, []
        for i in range(args.requests):
            model = tenants[i % len(tenants)]
            plen = int(rng.integers(max(args.prompt_len // 2, 2),
                                    args.prompt_len + 1))
            prompt = rng.integers(1, cfg.vocab, plen).tolist()
            vjobs.append((t, model, prompt, args.gen))
            t += float(rng.exponential(0.004))
        summary = drive_simulated(eng, clock, vjobs, dt=0.002)
    else:
        for i in range(args.requests):
            model = tenants[i % len(tenants)]
            plen = int(rng.integers(max(args.prompt_len // 2, 2),
                                    args.prompt_len + 1))
            prompt = rng.integers(1, cfg.vocab, plen).tolist()
            eng.submit(model, prompt, max_new_tokens=args.gen)
        summary = eng.run()
    print(f"engine: {args.requests} requests across {len(tenants)} models, "
          f"{args.kv_slots} KV slots each, weight arena {weight_slots} slots")
    print(format_summary(summary))
    if eng.telemetry is not None:
        print("health:", json.dumps(_json_safe(eng.health()),
                                    sort_keys=True))
    flush()
    if endpoint is not None:
        endpoint.close()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=ARCHS, default="gemma-7b")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=16)
    p.add_argument("--streaming", action="store_true",
                   help="serve through the ARAS streaming executor")
    p.add_argument("--arena-slots", type=int, default=3)
    p.add_argument("--engine", action="store_true",
                   help="continuous-batching engine, 2 tenants")
    p.add_argument("--requests", type=int, default=10,
                   help="engine: number of requests to submit")
    p.add_argument("--kv-slots", type=int, default=4,
                   help="engine: KV slots per tenant")
    p.add_argument("--weight-slots", type=int, default=0,
                   help="engine: weight arena slots (0 = n_layers+1)")
    p.add_argument("--turn-steps", type=int, default=8,
                   help="engine: tenant time-slice length in steps")
    p.add_argument("--queue-policy", choices=("fcfs", "sjf"), default="fcfs")
    p.add_argument("--kv-layout", choices=("slot", "paged"), default="slot",
                   help="engine: whole-sequence KV slots, or paged KV with "
                        "prefix sharing (removes the per-request max_seq "
                        "ceiling)")
    p.add_argument("--page-size", type=int, default=8,
                   help="engine: tokens per KV page (kv_layout=paged)")
    p.add_argument("--install-ticks-per-step", type=int, default=0,
                   help="engine: weight-install tick budget per step "
                        "(0 = instant installs at the turn boundary)")
    p.add_argument("--install-bytes-per-tick", type=int, default=1 << 16,
                   help="engine: wire bytes one install tick moves")
    p.add_argument("--overlap-installs", action="store_true",
                   help="engine: pipeline the next tenant's weight installs "
                        "under the current tenant's final decode steps "
                        "(needs --install-ticks-per-step > 0)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="engine: split prompt prefills into chunks of this "
                        "many tokens, spread across steps (0 = monolithic "
                        "per-prompt-length prefill)")
    p.add_argument("--prefill-token-budget", type=int, default=0,
                   help="engine: cap on prompt tokens one step may spend on "
                        "chunked prefill (0 = unbudgeted; needs "
                        "--prefill-chunk > 0 to matter)")
    p.add_argument("--bucket-growth", type=float, default=2.0,
                   help="engine: geometric growth of the prompt-length "
                        "bucket ladder tail chunks are padded to; bounds "
                        "distinct prefill jit traces at the ladder size "
                        "(<= 1 disables bucketing)")
    p.add_argument("--staging-growth", type=float, default=2.0,
                   help="engine: geometric growth of the staging-length "
                        "ladder — each chunked prefill stages into the "
                        "smallest rung covering its prompt instead of one "
                        "max-capacity buffer (<= 1 restores the single "
                        "max-capacity staging length)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="engine: radix-tree prefix cache over KV pages "
                        "(kv_layout=paged): finished requests donate their "
                        "pages, warm requests skip prefill chunks covered "
                        "by cached pages, LRU eviction frees pages on "
                        "demand")
    p.add_argument("--prefix-cache-pages", type=int, default=0,
                   help="engine: cap on retained prefix-cache pages per "
                        "tenant (0 = bounded only by on-demand eviction)")
    p.add_argument("--kernel-backend", choices=("xla", "pallas"),
                   default="xla",
                   help="engine: paged decode attention backend — 'pallas' "
                        "routes GQA decode through the paged-attention "
                        "kernel (skips fully-masked tail pages; interpret "
                        "mode off-TPU), 'xla' keeps the full-width gather "
                        "(needs --kv-layout paged for 'pallas')")
    p.add_argument("--no-fuse-sampling", action="store_true",
                   help="engine: split sampling back out of the jitted "
                        "paged decode step (fused on-device sampling is "
                        "the default — logits never leave the device)")
    p.add_argument("--trace-out", type=str, default="",
                   help="engine: write a Chrome-trace-format JSON of the "
                        "run (per-step component spans + request lifecycle "
                        "spans) to this path; load in chrome://tracing or "
                        "ui.perfetto.dev")
    p.add_argument("--metrics-json", type=str, default="",
                   help="engine: dump the final summary and the typed "
                        "metrics registry (counters/gauges/histograms) as "
                        "JSON to this path")
    p.add_argument("--wear-aware", type=float, nargs="?", const=1.0,
                   default=0.0, metavar="WEIGHT",
                   help="engine: blend install victim picking with per-slot "
                        "write pressure and hand out the coldest free KV "
                        "page first (Hamun-style wear leveling); optional "
                        "value is the blend weight (bare flag = 1.0, "
                        "0 = off, today's placement bit-for-bit)")
    p.add_argument("--fault-rate", type=float, default=0.0,
                   help="engine: seeded stuck-at fault probability per "
                        "physical write (weight slots + KV pages); faulted "
                        "units are retired and remapped with token "
                        "equivalence preserved (0 = no injection)")
    p.add_argument("--fault-seed", type=int, default=0,
                   help="engine: seed for the deterministic fault stream "
                        "(same seed + same schedule = same faults)")
    p.add_argument("--wear-json", type=str, default="",
                   help="engine: dump the per-plane wear map (write / "
                        "cell-flip / pulse counts per weight slot and KV "
                        "page, Gini, hottest-N, histogram) as JSON to this "
                        "path; artifacts also flush on Ctrl-C/SIGTERM")
    p.add_argument("--slo-ttft-p95", type=float, default=0.0,
                   metavar="SECONDS",
                   help="engine: TTFT p95 SLO target in seconds — "
                        "evaluated as short+long burn-rate windows, "
                        "breach/recover transitions emit trace instants "
                        "and flight-recorder dumps (0 = untracked)")
    p.add_argument("--slo-itl-p95", type=float, default=0.0,
                   metavar="SECONDS",
                   help="engine: worst inter-token-gap p95 SLO target in "
                        "seconds (0 = untracked)")
    p.add_argument("--slo-queue-wait-p95", type=float, default=0.0,
                   metavar="SECONDS",
                   help="engine: queue-wait p95 SLO target in seconds "
                        "(0 = untracked)")
    p.add_argument("--telemetry-window", type=int, default=128,
                   help="engine: sliding-window size for live windowed "
                        "percentiles (exact over the last N samples; "
                        "lifetime P² estimators ride along at O(1))")
    p.add_argument("--prom-out", type=str, default="",
                   help="engine: write Prometheus text exposition "
                        "(registry + live windows + SLO status) to this "
                        "path at exit — the textfile-collector mode")
    p.add_argument("--prom-port", type=int, default=0,
                   help="engine: serve /metrics on this localhost port "
                        "via a stdlib http.server daemon thread "
                        "(0 = no endpoint)")
    p.add_argument("--events-out", type=str, default="",
                   help="engine: append-mode JSONL event stream (per-step "
                        "window snapshots, request finishes, SLO "
                        "transitions) to this path")
    p.add_argument("--flight-recorder-steps", type=int, default=0,
                   help="engine: keep a flight-recorder ring of the last "
                        "N steps (StepRecords + trace events + health), "
                        "dumped to JSON on unit retirement, SLO breach, "
                        "suspected stall, SIGUSR1, or crash (0 = off)")
    p.add_argument("--flight-dir", type=str, default=".",
                   help="engine: directory flight-recorder dumps are "
                        "written into")
    p.add_argument("--stall-timeout-s", type=float, default=0.0,
                   help="engine: arm the step watchdog with this deadline "
                        "— a step that overruns it emits stall_suspected "
                        "+ a flight dump; observation only (0 = off)")
    p.add_argument("--virtual-clock", action="store_true",
                   help="engine: run on a VirtualClock with deterministic "
                        "simulated arrivals — every artifact is "
                        "byte-reproducible (the CI telemetry mode)")
    return p


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    setup_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if "decode_32k" not in supported_shapes(args.arch):
        raise SystemExit(f"{args.arch} is encoder-only: no decode path")

    if args.engine:
        _run_engine(args)
        return

    params = init_params(jax.random.PRNGKey(0), cfg)
    data = DataConfig(seq_len=args.prompt_len, global_batch=args.batch)
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, data, 0).items()}

    if args.streaming:
        from repro.streaming.executor import StreamingExecutor
        ex = StreamingExecutor(params, cfg, arena_slots=args.arena_slots,
                               plan_tokens=args.batch * args.prompt_len)
        t0 = time.perf_counter()
        logits, m = ex.forward(batch)
        print(f"streaming forward: {m['wall_s']*1e3:.1f} ms, "
              f"wire {m['wire_bytes']/1e6:.2f} MB vs raw "
              f"{m['raw_bytes']/1e6:.2f} MB "
              f"(skip {m['mean_skip']:.1%}, center={int(m['reuse_center'])}); "
              f"plan overlap speedup {m['plan_overlap_speedup']:.2f}×")
        return

    prefix = cfg.prefix_len if cfg.input_mode == "prefix_vlm" else 0
    cache_len = args.prompt_len + prefix + args.gen
    prefill_fn = cached_prefill_step(cfg, cache_len)
    serve_fn = cached_serve_step(cfg)

    t0 = time.perf_counter()
    logits, caches = prefill_fn(params, batch)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    tokens = [jnp.argmax(logits, -1).astype(jnp.int32)]
    t0 = time.perf_counter()
    pos = args.prompt_len + prefix
    for i in range(args.gen - 1):
        logits, caches = serve_fn(params, tokens[-1], caches,
                                  jnp.int32(pos + i))
        tokens.append(jnp.argmax(logits, -1).astype(jnp.int32))
    tokens[-1].block_until_ready()
    t_decode = time.perf_counter() - t0

    out = np.stack([np.asarray(t) for t in tokens], 1)
    print(f"prefill {args.batch}×{args.prompt_len}: {t_prefill*1e3:.1f} ms; "
          f"decode {args.gen-1} steps: {t_decode*1e3:.1f} ms "
          f"({(args.gen-1)*args.batch/max(t_decode,1e-9):.1f} tok/s)")
    print("sampled token ids:", out[0, :12], "...")


if __name__ == "__main__":
    main()
