"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` that JAX reports.  A device that is not in the table is an
error: a roofline share or an MFU against a guessed peak means nothing.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bytes_s: float     # bytes/s
    hbm_bytes: float       # bytes
    source: str

    @property
    def ridge(self) -> float:
        """Flops per byte at which a kernel turns from memory- to
        compute-bound."""
        return self.bf16_flops / self.hbm_bytes_s


_V5E = Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes_s=819e9,
             hbm_bytes=16e9,
             source='Google Cloud documentation, "TPU v5e"')

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
