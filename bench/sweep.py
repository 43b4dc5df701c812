"""Find an open-loop mix's knee once, on the chip.

    python3 bench/sweep.py --workload minicpm-2b.chat \
        --rates 0.45,0.6,0.75,0.9 --seconds 51 --ttft-limit 1.0

The cell's engine is built once.  Each rate offers the cell's own mix with
only `rate_per_s` changed, through the benchmark's `Driver`, lead-in and
window as a run has them; the window is read by the metric modules
(`bench/metrics/<name>.py`), and the engine drains before the next rate.

The knee is the highest rate whose `ttft_p90_s` is within `--ttft-limit`
and whose queue did not grow over the window: the scheduler's queue at
the window's close is at most one longer than at its middle.  The cell's
traffic file is then set, by hand, to four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

READ = ("ttft_p50_s", "ttft_p90_s", "queue_wait_p90_s", "itl_p95_s",
        "output_tok_s", "preemptions")


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=51.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--ttft-limit", type=float, required=True)
    args = p.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("bench/sweep.py needs a TPU", file=sys.stderr)
        return 2
    from bench import families, harness, traffic
    from bench.record import Run, read_all
    from bench.weights import engine_params, make_weights
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    conf = harness.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    if mix["loop"] != "open":
        raise SystemExit("a closed loop has no rate to sweep")
    hf = conf["config"]
    model_cfg = families.of(conf).model_config(cell["config"], hf)
    params = engine_params(make_weights(args.seed, conf), conf, model_cfg)
    eng = harness.build_engine(conf, model_cfg, params)
    del params
    harness.warm_up(eng, conf, harness.warm_lengths(eng, conf, mix),
                    hf["vocab_size"])
    within = []
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, rate_per_s=rate)
        d = harness.Driver(eng, m, traffic.requests(m, args.seed,
                                                    args.seconds),
                           hf["vocab_size"], harness.tenants(conf))
        queue = []
        d.run(time.perf_counter(), args.seconds,
              on_boundary=lambda now: queue.append(
                  (now, len(eng.scheduler.queue))))
        w0, w1 = d.window
        mid = min(queue, key=lambda q: abs(q[0] - (w0 + w1) / 2))[1]
        close = len(eng.scheduler.queue)
        run = Run(conf=conf, peaks=None, window=d.window,
                  tracked=d.tracked, steps=d.steps, setup_s=0.0,
                  compile_events=[],
                  residency_build_s=eng.residency_build_s)
        got = read_all(run, READ)
        ok = got["ttft_p90_s"] <= args.ttft_limit and close <= mid + 1
        within += [rate] if ok else []
        print(json.dumps(dict(
            rate=rate, due=len(run.window_requests),
            done=sum(t.done for t in run.window_requests),
            queue_mid=mid, queue_close=close, steps=len(run.window_steps),
            within=ok, **got)), flush=True)
        while eng.has_work():      # drain before the next rate
            eng.step()
    print(json.dumps({"knee": max(within, default=None),
                      "ttft_limit_s": args.ttft_limit}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
