"""What the yardsticks share: the byte widths and the least time of a
piece of work on a chip.  Operations and bytes are computed from shapes
by each model family (`bench/families/<name>.py`, `Shape`); nothing here
reads the program.

Conventions: a multiply-add is 2 flops; bf16 is 2 bytes; attention is
counted causally, a token at position p attending p + 1 keys.
"""
from __future__ import annotations

BF16 = 2
INT32 = 4


def least_time(nbytes: float, flops: float, peaks) -> tuple:
    """(seconds, bound): the larger of bytes over bandwidth and flops over
    the bf16 peak, and which of the two it is."""
    t_mem = nbytes / peaks.hbm_bytes_s
    t_cmp = flops / peaks.bf16_flops
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
