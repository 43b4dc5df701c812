"""prefill_ms_per_ktok: what prefill adds to a step, per 1000 prompt
tokens: the wall time of the window's steps that ran prefill chunks, less
as many median steps that only decoded, over their prompt tokens.  Taken
from step wall times because the host waits for a chunk wherever the next
readback falls, often inside the decode span."""
from bench.record import percentile


def read(run):
    steps = run.window_steps
    pure = [s.t1 - s.t0 for s in steps
            if s.decode_positions and s.prefill_tokens == 0]
    mixed = [s for s in steps if s.prefill_tokens > 0]
    if not pure or not mixed:
        return None
    base = percentile(pure, 50)
    extra = sum(s.t1 - s.t0 - base for s in mixed)
    return 1e3 * extra / (sum(s.prefill_tokens for s in mixed) / 1e3)
