"""ttft_p50_s: median, over every request due in the window, of the time
from when it was due to when the step that produced its first token
returned.  A request with no first token when the window closes counts
its wait so far."""
from bench.record import percentile


def waits(run):
    end = run.window[1]
    return [(t.stamps[0] if t.stamps and t.stamps[0] <= end else end)
            - t.due for t in run.window_requests]


def read(run):
    return percentile(waits(run), 50)
