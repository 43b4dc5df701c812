"""Record the small CPU profiler trace of a traced engine that the scope
readers' tests read (`bench/tests/test_scopes.py`).

    JAX_PLATFORMS=cpu python3 bench/testdata/make_engine_trace.py

A tiny paged engine with an enabled `Tracer` serves two requests; once
their prompts are in, three steps are profiled, each inside a
`bench.step` annotation as `bench/harness.py` makes them, separated
by 20 ms sleeps inside `bench.wait_arrival`.  Each step's engine phases
land on the host line as `engine.<component>`.
"""
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import families, harness  # noqa: E402
from bench.tests.conftest import SMOKE_CONF  # noqa: E402
from bench.weights import engine_params, make_weights  # noqa: E402
from repro.serving import Tracer  # noqa: E402

OUT = Path(__file__).resolve().parent / "engine_cpu_trace.xplane.pb"

# the benchmark tests' smoke configuration, with a small pool, the XLA
# decode path and 16-token chunks
CONF = dict(SMOKE_CONF, engine={
    "dtype": "bfloat16", "tenants": ["model"], "decode_rows": 4,
    "page_size": 8, "kv_pages": 32, "kernel_backend": "xla",
    "prefill_chunk": 16, "prefill_token_budget": 16,
    "max_prefill_per_step": 2, "bucket_min": 8, "bucket_growth": 2.0,
    "staging_growth": 2.0,
})


def main():
    cfg = families.of(CONF).model_config("engine-trace", CONF["config"])
    params = engine_params(make_weights(5, CONF), CONF, cfg)
    eng = harness.build_engine(CONF, cfg, params, Tracer())
    for n in (10, 13):
        eng.submit("model", [1 + (3 * n + j) % 499 for j in range(n)],
                   max_new_tokens=12)
    for _ in range(4):          # prefill both, compile the decode step
        eng.step()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step()
        with jax.profiler.TraceAnnotation("bench.wait_arrival"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, OUT)
    shutil.rmtree(d)
    print(OUT, OUT.stat().st_size)


if __name__ == "__main__":
    main()
