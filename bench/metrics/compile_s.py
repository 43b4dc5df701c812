"""compile_s: seconds JAX spent tracing, lowering and compiling programs
or loading them from the persistent cache during set-up
(`/jax/core/compile/*_duration` events before the window)."""


def read(run):
    w0 = run.window[0]
    return sum(d for t, d, _ in run.compile_events if t < w0)
