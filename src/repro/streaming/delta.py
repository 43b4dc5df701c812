"""Delta-encoded INT8 weight installs with §V-C mean-centering.

Host side keeps every layer's weights as uint8 codes (plus dequant params).
Installing layer Y into an arena slot holding layer X ships
``delta = codes_Y - codes_X`` (int16 host-side, int8 stream after the cell
decomposition); cells whose 2-bit planes are equal are skipped entirely via
a run-length skip list, so bytes-on-wire track the paper's pulse counts.

The §V-C re-encoding (shift every layer's code mean to a common Center,
compensated through the zero point — `repro.core.weight_reuse`) maximizes
equal MSB cells across layers and therefore the skip ratio.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.weight_reuse import encode_network
from repro.xbar.cells import CELLS_PER_WEIGHT


def _cells(codes: np.ndarray) -> np.ndarray:
    c = codes.astype(np.int16).reshape(-1, 1)
    shifts = np.arange(CELLS_PER_WEIGHT) * 2
    return (c >> shifts) & 0x3


# Counting cell deltas byte pair by byte pair: every (old, new) code pair
# contributes a fixed multiset of per-cell level deltas, so one histogram of
# the joint byte pairs gives the same counts as expanding every code into
# its cells, in one pass over the codes.
_CHUNK = 1 << 20


def _pair_deltas() -> np.ndarray:
    """(65536, 7) table: row old*256 + new counts the 2-bit cells of that
    byte pair whose level changes by each delta in [-3, 3] (column
    delta + 3)."""
    cells = _cells(np.arange(256, dtype=np.uint8))
    d = (cells[None, :, :] - cells[:, None, :] + 3).reshape(65536, -1)
    table = np.zeros((65536, 7), np.int64)
    for c in range(d.shape[1]):
        np.add.at(table, (np.arange(65536), d[:, c]), 1)
    return table


_PAIR_DELTAS = _pair_deltas()
_ABS_DELTA = np.abs(np.arange(7) - 3)


def cell_delta_hist(old: Optional[np.ndarray], new: np.ndarray) -> np.ndarray:
    """Histogram (7,) of per-cell level deltas new - old (bin delta + 3)
    over equal-length code arrays; old None = erased (every cell at level
    0).  Equal to bincounting `_cells(new) - _cells(old)`."""
    joint = np.zeros(65536, np.int64)
    for s in range(0, new.size, _CHUNK):
        pair = new[s:s + _CHUNK]
        if old is not None:
            pair = (old[s:s + _CHUNK].astype(np.uint16) << 8) | pair
        joint += np.bincount(pair, minlength=65536)
    return joint @ _PAIR_DELTAS


def _flips_from_hist(hist: np.ndarray, skip_equal: bool) -> Tuple[int, int]:
    if skip_equal:
        return int(hist.sum() - hist[3]), int(hist @ _ABS_DELTA)
    return int(hist.sum()), int(hist @ np.maximum(_ABS_DELTA, 1))


def _wire_from_hist(hist: np.ndarray) -> Tuple[int, float]:
    counts = hist.astype(np.float64)
    n = int(hist.sum())
    probs = counts[counts > 0] / n
    entropy_bits = float(-(probs * np.log2(probs)).sum())
    payload = int(np.ceil(n * entropy_bits / 8.0)) + 16
    skip = float(counts[3] / n)  # Δ == 0
    return payload, skip


def flip_counts(old: Optional[np.ndarray], new: np.ndarray, *,
                skip_equal: bool = True) -> Tuple[int, int]:
    """(cells programmed, programming pulses) to put `new` codes into a
    region holding `old` (None = erased region: every cell at level 0; a
    `new` longer than `old` programs its tail from erased too).

    skip_equal=True is the §V-C device write: 2-bit planes whose level is
    unchanged are skipped entirely (0 pulses), and a changed cell costs
    |Δ level| incremental SET/RESET pulses (`repro.xbar.cells.pulse_count`,
    the paper's Fig. 13 writing-activity metric).  skip_equal=False is the
    baseline programmer: every cell is rewritten, an unchanged level still
    costing its one write/verify pulse."""
    n = 0 if old is None else min(old.size, new.size)
    hist = cell_delta_hist(None, new[n:])
    if n:
        hist = hist + cell_delta_hist(old[:n], new[:n])
    return _flips_from_hist(hist, skip_equal)


def delta_bytes(old: np.ndarray, new: np.ndarray) -> Tuple[int, float]:
    """Bytes-on-wire for an entropy-coded cell-delta stream + skip ratio.

    The install ships per-cell level deltas in [-3, 3].  A range coder on
    that stream achieves the empirical entropy H(Δ) bits/cell (+ a 16-byte
    frequency table); mean-centering (§V-C) concentrates Δ at 0, which is
    exactly what shrinks H — the information-theoretic counterpart of
    skipped ReRAM pulses.  RLE framing was measured strictly worse on
    fragmented skip patterns (isolated equal cells cost a run token each)."""
    return _wire_from_hist(cell_delta_hist(old, new))


@dataclasses.dataclass
class LayerWeights:
    """One layer's quantized tensors, flattened into a single code vector for
    transfer accounting plus per-tensor views for compute."""

    name: str
    codes: np.ndarray                 # uint8, concatenated
    shapes: List[Tuple[int, ...]]
    sizes: List[int]
    scales: List[np.ndarray]
    zero_points: List[np.ndarray]     # offset-compensated (Eq. 7)
    offset: int = 0

    def tensor(self, i: int) -> np.ndarray:
        start = sum(self.sizes[:i])
        return self.codes[start:start + self.sizes[i]].reshape(self.shapes[i])

    def dequant(self, i: int) -> np.ndarray:
        t = self.tensor(i).astype(np.float32)
        return (t - self.zero_points[i]) * self.scales[i]


class QuantizedStore:
    """Host-resident quantized model with cross-layer re-encoding.

    ``offset_groups`` (optional, one label per layer) pools the §V-C offset
    decision: all layers sharing a label get ONE offset computed from their
    pooled codes.  A multi-tenant store groups aligned layers of model
    variants this way — per-layer offsets would shift near-identical tenant
    copies by slightly different amounts (their code means differ by
    rounding), turning a near-zero delta stream into a uniform ±1 shift of
    every cell and destroying the cross-tenant reuse it exists to enable.
    """

    def __init__(self, layers: Iterable[Tuple[str, List[np.ndarray]]],
                 reuse: bool = True, max_clip_rate: float = 4e-3,
                 offset_groups: Optional[Sequence[object]] = None):
        # Quantize each tensor per-tensor (uint8 affine).
        self.layers: List[LayerWeights] = []
        concat_codes = []
        for name, tensors in layers:
            codes, shapes, sizes, scales, zps = [], [], [], [], []
            for w in tensors:
                lo, hi = float(w.min()), float(w.max())
                scale = max(hi - lo, 1e-8) / 255.0
                zp = -lo / scale
                c = np.clip(np.round(w / scale + zp), 0, 255).astype(np.uint8)
                codes.append(c.reshape(-1))
                shapes.append(w.shape)
                sizes.append(w.size)
                scales.append(np.float32(scale))
                zps.append(np.float32(zp))
            cat = np.concatenate(codes) if codes else np.zeros(0, np.uint8)
            self.layers.append(LayerWeights(name, cat, shapes, sizes, scales, zps))
            concat_codes.append((name, cat))

        self.center: Optional[int] = None
        if reuse:
            if offset_groups is None:
                encs, center = encode_network(concat_codes, enabled=True,
                                              max_clip_rate=max_clip_rate)
                offsets = [e.offset for e in encs]
            else:
                assert len(offset_groups) == len(self.layers)
                groups = list(dict.fromkeys(offset_groups))  # stable order
                # Subsample members before pooling: offsets only need group
                # means/histograms (which converge long before 256k samples)
                # and a full concatenation would transiently duplicate the
                # whole multi-tenant code store.
                cap = 1 << 18
                pooled = []
                for g in groups:
                    member = [cat[::max(1, cat.size // cap)]
                              for (_, cat), gg in zip(concat_codes,
                                                      offset_groups)
                              if gg == g and cat.size]
                    pooled.append((str(g), np.concatenate(member)
                                   if member else np.zeros(1, np.uint8)))
                encs, center = encode_network(pooled, enabled=True,
                                              max_clip_rate=max_clip_rate)
                off_of = {g: e.offset for g, e in zip(groups, encs)}
                # Per-member accuracy guard: encode_network only checked the
                # pooled clip rate; a member sitting near the code extremes
                # could clip far above it.  Zero the WHOLE group's offset
                # (not just the member) so aligned tenants stay aligned.
                worst = {g: 0.0 for g in groups}
                for (_, cat), g in zip(concat_codes, offset_groups):
                    off = off_of[g]
                    if cat.size and off:
                        clipped = (np.count_nonzero(cat > 255 - off)
                                   if off > 0 else
                                   np.count_nonzero(cat < -off))
                        worst[g] = max(worst[g], clipped / cat.size)
                off_of = {g: (0 if worst[g] > max_clip_rate else o)
                          for g, o in off_of.items()}
                offsets = [off_of[g] for g in offset_groups]
            self.center = center
            for lw, off in zip(self.layers, offsets):
                if off:
                    shifted = np.clip(lw.codes.astype(np.int32) + off,
                                      0, 255).astype(np.uint8)
                    lw.codes = shifted
                    lw.offset = off
                    # Eq. 7: compensate through the zero point.
                    lw.zero_points = [zp + off for zp in lw.zero_points]

    def pair_cost(self, resident: Optional[int], incoming: int, *,
                  skip_equal: bool = True) -> Tuple[int, float, int, int]:
        """What putting layer `incoming` into a slot holding `resident`
        (None = cold slot) costs, from one pass over the codes: (wire bytes,
        skip ratio) as `install_cost`, and (cells programmed, programming
        pulses) the DEVICE spends, as `flip_counts`.  The device side is
        independent of the wire encoding: even when the raw code stream
        ships (delta entropy exceeded 2 bits/cell), a skip_equal programmer
        still read-verifies and skips equal 2-bit planes.  skip_equal=False
        models the no-reuse baseline that rewrites every cell."""
        new = self.layers[incoming].codes
        old = None if resident is None else self.layers[resident].codes
        n = 0 if old is None else min(old.size, new.size)
        tail = cell_delta_hist(None, new[n:])
        if n == 0:
            return (new.size, 0.0,
                    *_flips_from_hist(tail, skip_equal))
        head = cell_delta_hist(old[:n], new[:n])
        wire, skip = _wire_from_hist(head)
        return (wire + (new.size - n), skip,
                *_flips_from_hist(head + tail, skip_equal))

    def install_cost(self, resident: Optional[int], incoming: int
                     ) -> Tuple[int, float]:
        """(bytes-on-wire, skip ratio) to put layer `incoming` into a slot
        currently holding `resident` (None = cold slot → full stream)."""
        new = self.layers[incoming].codes
        if resident is None:
            return new.size, 0.0
        old = self.layers[resident].codes
        n = min(old.size, new.size)
        if n == 0:
            return new.size, 0.0
        b, skip = delta_bytes(old[:n], new[:n])
        return b + (new.size - n), skip
