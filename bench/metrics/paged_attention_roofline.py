"""paged_attention_roofline: the least time the chip could take for the
paged-attention calls of the traced steps (their live pages' bytes over
HBM bandwidth, or their flops over the bf16 peak, whichever is larger;
the configuration's family's yardstick, `bench/families`) over the summed
device time of the kernel's operations (`pallas_call` name
`paged_attention`), in percent."""
from bench import flops, trace_reduce

KERNEL = r"paged_attention"


def bound(run):
    """(least seconds, 'memory' | 'compute', kernel seconds) or None."""
    tw = run.trace
    # no device number without a known chip's peaks (never from a CPU run)
    if tw is None or run.peaks is None:
        return None
    shape = run.shape
    page = run.conf["engine"]["page_size"]
    nbytes = nflops = 0
    for s in tw.steps:
        b, f = shape.paged_attention_call(s.decode_positions, page)
        nbytes += b * shape.n_layers
        nflops += f * shape.n_layers
    kernel_s = trace_reduce.kernel_ns(tw.trace, KERNEL, tw.lo, tw.hi) * 1e-9
    if nbytes == 0 or kernel_s <= 0:
        return None
    least, which = flops.least_time(nbytes, nflops, run.peaks)
    return least, which, kernel_s


def read(run):
    got = bound(run)
    if got is None:
        return None
    least, _, kernel_s = got
    return 100.0 * least / kernel_s
