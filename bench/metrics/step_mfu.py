"""step_mfu: model flops of the traced steps over the traced window's
length times the chip's bf16 peak, in percent.  Model flops count every
prefill and decoded token through every layer's matmuls, causal attention
over its context, and the LM head for each decoded token and each
chunk's last position (the configuration's family's yardstick,
`bench/families`)."""


def read(run):
    tw = run.trace
    # no device number without a known chip's peaks (never from a CPU run)
    if tw is None or run.peaks is None or tw.hi <= tw.lo or not tw.steps:
        return None
    shape = run.shape
    total = 0
    for s in tw.steps:
        total += sum(shape.decode_token_flops(p) for p in s.decode_positions)
        total += sum(shape.prefill_chunk_flops(start, n)
                     for start, n in s.chunks)
    return 100.0 * total / (tw.seconds * run.peaks.bf16_flops)
