"""queue_wait_p90_s: 90th percentile, over every request due in the
window, of the time from when it was due to its first prefill chunk
(`Request.prefill_start_t`); a request not started when the window closes
counts its wait so far."""
from bench.record import percentile


def read(run):
    end = run.window[1]
    waits = []
    for t in run.window_requests:
        start = t.req.prefill_start_t
        waits.append((start if start is not None and start <= end else end)
                     - t.due)
    return percentile(waits, 90)
