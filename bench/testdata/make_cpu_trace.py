"""Record the small CPU profiler trace the trace-reduction tests read.

    JAX_PLATFORMS=cpu python3 bench/testdata/make_cpu_trace.py

Three steps, each a jitted matmul (`dot_general`) inside a `bench.step`
annotation, separated by 20 ms sleeps inside `bench.wait_arrival`: the
device is idle in those sleeps, and the longest gaps must be labelled
with them.
"""
import glob
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).resolve().parent / "cpu_trace.xplane.pb"


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait_arrival"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, OUT)
    shutil.rmtree(d)
    print(OUT, OUT.stat().st_size)


if __name__ == "__main__":
    main()
