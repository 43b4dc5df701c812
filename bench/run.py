"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload minicpm-2b.chat --seed 7 --seconds 51 \
        --trace 0

The cell (`BENCHMARK.json` `workloads`) names a configuration
(`bench/configs/<name>.json`) and a traffic mix (`bench/traffic/<name>.json`).
The run makes the weights on the device from `--seed`, builds a
`ServingEngine` with the configuration's tenants, warms up every prefill
shape the mix can reach, offers the mix's lead-in and then measures for
`--seconds`.  With `--trace 0` it prints the cell's end-to-end metrics;
with `--trace 1` it profiles a few seconds of the window and prints the
per-layer metrics, the device's busy time and a breakdown.  Each metric is
read by `bench/metrics/<name>.py`.

After the window the engine is freed and a sample of the finished
requests is compared with the float32 reference (`bench/correctness.py`).
The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device, [breakdown,] [controls,] checks.  The checks,
each number with its limit, are also the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
TRACE_S = 4.0           # profiled seconds in the middle of the window


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", default="",
                   help="also judge the control (comma-separated modes: "
                        "int8, fp8) in the program's place, by the same "
                        "checks, on the compared requests; for setting "
                        "the limits, never in a measured run")
    p.add_argument("--trace-dir", default="",
                   help="keep the profile here (default: a temporary "
                        "directory, removed after it is read)")
    return p.parse_args(argv)


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metric_names(bench: dict, cell: dict, traced: bool) -> dict:
    """{name: unit} of the metrics this cell reports in this kind of run."""
    out = {}
    for m in bench["per_layer" if traced else "end_to_end"]:
        if cell["name"] in m.get("workloads", [cell["name"]]):
            out[m["name"]] = m["unit"]
    return out


def log(*a):
    print(*a, flush=True)


class Compiles:
    """Every compile-time event JAX reports, with the clock time it came."""

    def __init__(self, clock):
        import jax

        self.events = []
        self.clock = clock

        def on_duration(event, duration, **_):
            if event in COMPILE_EVENTS:
                self.events.append((self.clock(), float(duration), event))

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def between(self, lo, hi):
        """(clock, seconds) of the programs compiled or loaded in [lo, hi)."""
        return [(t, d) for t, d, e in self.events
                if lo <= t < hi and e == COMPILE_EVENTS[-1]]


class Profiler:
    """Starts `jax.profiler` at the first step boundary after `start` and
    stops it at the first after `start + seconds`."""

    def __init__(self, driver, start, seconds, directory):
        self.driver, self.start, self.stop_at = driver, start, start + seconds
        self.dir = directory
        self.state = "idle"

    def __call__(self, now):
        import jax

        if self.state == "idle" and now >= self.start:
            jax.profiler.start_trace(self.dir)
            self.driver.tracing = True
            self.state = "on"
        elif self.state == "on" and now >= self.stop_at:
            self.finish()

    def finish(self):
        import jax

        if self.state == "on":
            self.driver.tracing = False
            jax.profiler.stop_trace()
            self.state = "done"


def main(argv=None) -> int:
    args = parse(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = cell_of(bench, args.workload)
    from bench import harness, traffic

    conf = harness.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    units = metric_names(bench, cell, bool(args.trace))
    controls = [m for m in args.control.split(",") if m]
    result = run_cell(cell, conf, mix, units, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      trace_dir=args.trace_dir, controls=controls)
    if not isinstance(result, dict):
        return result
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(cell: dict, conf: dict, mix: dict, units: dict, *, seed: int,
             seconds: float, trace: bool = False, trace_dir: str = "",
             controls=(), require_tpu: bool = True, after_build=None):
    """One run of a cell; returns the result dict, or an exit code when
    the device does not fit.  `require_tpu=False` and `after_build(eng)`
    (which may break the engine underneath) are for the tests."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        print(f"bench/run.py measures a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"{cell['name']} needs {cell['chips']} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from bench import correctness, families, harness, traffic, trace_reduce
    from bench.peaks import peaks_for
    from bench.record import Run, TraceWindow, read_all
    from bench.weights import engine_params, make_weights
    from repro.launch.compile_cache import setup_compile_cache

    peaks = peaks_for(dev.device_kind) if require_tpu else None
    cache_dir = setup_compile_cache()
    clock = time.perf_counter
    compiles = Compiles(clock)
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"compile cache {cache_dir}")

    hf = conf["config"]
    model_cfg = families.of(conf).model_config(cell["config"], hf)
    w = make_weights(seed, conf)
    params = engine_params(w, conf, model_cfg)
    del w
    tracer = None
    if trace:
        from repro.serving import Tracer
        tracer = Tracer(clock=clock)
    eng = harness.build_engine(conf, model_cfg, params, tracer)
    del params
    if after_build is not None:
        after_build(eng)
    lengths = harness.warm_lengths(eng, conf, mix)
    t0 = clock()
    harness.warm_up(eng, conf, lengths, hf["vocab_size"])
    log(f"warm-up: {len(lengths)} prompt lengths {lengths} in "
        f"{clock() - t0:.3f} s; residency store {eng.residency_build_s:.3f} s")

    specs = traffic.requests(mix, seed, seconds)
    annotate = jax.profiler.TraceAnnotation if trace else None
    driver = harness.Driver(eng, mix, specs, hf["vocab_size"],
                            harness.tenants(conf), clock=clock,
                            annotate=annotate)
    start = clock()
    w0 = start + float(mix["lead_in_s"])
    profiler = None
    keep_trace = bool(trace_dir)
    if trace:
        trace_dir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        span = min(TRACE_S, seconds)
        profiler = Profiler(driver, w0 + (seconds - span) / 2, span,
                            trace_dir)
    driver.run(start, seconds, on_boundary=profiler)
    if profiler is not None:
        profiler.finish()
        profiler = None
    w0, w1 = driver.window
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    run = Run(conf=conf, peaks=peaks, window=(w0, w1),
              tracked=driver.tracked, steps=driver.steps,
              setup_s=w0 - T_PROCESS, compile_events=compiles.events,
              residency_build_s=eng.residency_build_s)
    win_req = run.window_requests
    late = sorted(t.sent - t.due for t in win_req)
    in_win = compiles.between(w0, w1)
    lead_c = compiles.between(start, w0)
    setup_c = compiles.between(T_PROCESS, w0)
    preempt = sum(t.req.preemptions for t in driver.tracked)
    log(f"requests: {len(win_req)} due in the window, "
        f"{sum(1 for t in win_req if t.done)} finished in it; "
        f"{len(driver.tracked)} sent in all; {len(run.window_steps)} steps "
        f"in the window; preemptions {preempt}")
    if late:
        log(f"generator lateness: p50 {late[len(late) // 2]:.6f} s, "
            f"max {late[-1]:.6f} s")
    log(f"compiles: {len(setup_c)} in set-up "
        f"({sum(d for _, d in setup_c):.3f} s), {len(lead_c)} in the "
        f"lead-in, {len(in_win)} in the window")
    log(f"device peak_bytes_in_use {peak} ({peak / 2**30:.3f} GiB of "
        f"{stats.get('bytes_limit', 0) / 2**30:.3f} GiB)")

    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        tr = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        span = trace_reduce.annotation_span(tr, "bench.step")
        traced = [s for s in driver.steps if s.traced]
        if span is None or not traced:
            print("the profiled window holds no engine step", file=sys.stderr)
            return 1
        run.trace = TraceWindow(tr, span[0], span[1], traced)
        busy = trace_reduce.busy_ns(tr, *span) * 1e-9
        device["busy_s"] = busy
        device["window_s"] = run.trace.seconds
        breakdown = {"device_ops": trace_reduce.top_ops(tr, *span),
                     "idle_gaps": trace_reduce.idle_gaps(tr, *span)}
        log(f"trace: {len(traced)} steps, window {run.trace.seconds:.6f} s, "
            f"device busy {busy:.6f} s, {len(tr.device_ops)} device ops")
        if peaks is not None:
            from bench.metrics.paged_attention_roofline import bound
            got = bound(run)
            if got is not None:
                log(f"paged_attention: least time {got[0]:.6f} s "
                    f"({got[1]}-bound), kernel device time {got[2]:.6f} s")

    values = read_all(run, units)
    metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
    for k, m in metrics.items():
        log(f"metric {k} = {m['value']!r} {m['unit']}")

    # ---- correctness, once the engine's device state is freed
    vocab = hf["vocab_size"]
    finished = [t for t in driver.tracked if t.req.status.name == "FINISHED"]
    bad = [t for t in finished
           if len(t.req.generated) != t.spec.max_new
           or not all(0 <= x < vocab for x in t.req.generated)]
    rejected = sum(1 for t in win_req if t.req.status.name == "REJECTED")
    pool = [t for t in finished if t.window] or finished
    cc = conf["correctness"]
    seqs = correctness.sample(
        [correctness.Seq(t.spec.prompt(vocab), list(t.req.generated), t.row)
         for t in pool], seed, cc["sample_tokens"], cc["min_requests"],
        cc["max_requests"])
    del driver, eng, run, tracer
    gc.collect()
    log(f"device bytes in use with the engine freed: "
        f"{(dev.memory_stats() or {}).get('bytes_in_use', 0)}")
    t0 = clock()
    w = make_weights(seed, conf)
    gap = correctness.gaps(w, conf, seqs)
    log(f"reference: {len(seqs)} requests, {gap.size} served tokens in "
        f"{clock() - t0:.3f} s")
    faults = {"bad": len(bad), "rejected": rejected,
              "compiles_in_window": len(in_win)}
    correct, checks = correctness.judge(gap, cc, **faults)
    log(_verdict("program", correct, checks, gap))
    judged_controls = {}
    for mode in controls:
        ctl = correctness.gaps(w, conf, seqs, mode=mode)
        ok, c_checks = correctness.judge(ctl, cc, **faults)
        judged_controls[mode] = {"correct": ok, "checks": c_checks}
        log(_verdict(f"control {mode}", ok, c_checks, ctl))
    del w

    result = {"correct": correct, "attempted": len(win_req),
              "failed": rejected, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if judged_controls:
        result["controls"] = judged_controls
    result["checks"] = checks
    return result


def _verdict(who: str, correct: bool, checks: dict, gap) -> str:
    return (f"{who}: correct {correct}; gap_max {checks['gap_max']['value']!r}"
            f" gap_mean {checks['gap_mean']['value']!r}; served token below "
            f"the reference's best at {int((gap > 0).sum())} of {gap.size}")


if __name__ == "__main__":
    sys.exit(main())
