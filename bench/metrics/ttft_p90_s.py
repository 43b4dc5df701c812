"""ttft_p90_s: 90th percentile, over every request due in the window, of
the time from when it was due to when the step that produced its first
token returned.  A request with no first token when the window closes
counts its wait so far."""
from bench.metrics.ttft_p50_s import waits
from bench.record import percentile


def read(run):
    return percentile(waits(run), 90)
