"""itl_p95_s: 95th percentile of the gaps between consecutive output
tokens of a request, over every gap whose later token became visible in
the window (steps that carry a prefill chunk included)."""
from bench.record import percentile


def read(run):
    gaps = [b - a for t in run.tracked
            for a, b in zip(t.stamps, t.stamps[1:]) if run.in_window(b)]
    return percentile(gaps, 95)
