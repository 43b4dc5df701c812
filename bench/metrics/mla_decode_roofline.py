"""mla_decode_roofline: the least time the chip could take for the latent
decode calls of the traced steps (their live pages' bytes over HBM
bandwidth, or their flops over the bf16 peak, whichever is larger; the
configuration's family's yardstick `paged_attention_call` for every
layer, `bench/families`) over the summed device time of the latent
kernel's operations (`pallas_call` name `mla_decode`), in percent.  A
program without that kernel reads nothing."""
from bench import flops, trace_reduce

KERNEL = r"mla_decode"


def read(run):
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
    least, _ = flops.least_time(nbytes, nflops, run.peaks)
    return 100.0 * least / kernel_s
