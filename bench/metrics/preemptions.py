"""preemptions: requests the engine preempted (KV pool exhausted at a
decode step, or at a prefill chunk) over the run, summed from
`Request.preemptions`; each one re-prefills its prompt and tokens."""


def read(run):
    return float(sum(t.req.preemptions for t in run.tracked))
