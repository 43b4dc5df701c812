"""What one run leaves for the metric readers (`bench/metrics/<name>.py`),
and the helpers they share.  Each reader is a module with one function,
`read(run) -> float | None`; None means the run had nothing to read, and
the harness then leaves the metric out of the result line."""
from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import families
from bench.harness import StepRec, Tracked


@dataclasses.dataclass
class TraceWindow:
    trace: object                   # bench.trace_reduce.Trace
    lo: float                       # ns, on the trace's clock
    hi: float
    steps: List[StepRec]            # the steps run inside it

    @property
    def seconds(self) -> float:
        return (self.hi - self.lo) * 1e-9


@dataclasses.dataclass
class Run:
    conf: dict                      # the configuration file
    peaks: object                   # bench.peaks.Peaks
    window: Tuple[float, float]     # host clock, seconds
    tracked: List[Tracked]
    steps: List[StepRec]
    setup_s: float
    compile_events: List[Tuple[float, float, str]]   # (clock, seconds, event)
    residency_build_s: float
    trace: Optional[TraceWindow] = None

    @functools.cached_property
    def shape(self):
        """The configuration's yardstick (its family's `Shape`)."""
        return families.shape(self.conf)

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    @property
    def window_requests(self) -> List[Tracked]:
        return [t for t in self.tracked if t.window]

    @property
    def window_steps(self) -> List[StepRec]:
        return [s for s in self.steps if self.in_window(s.t0)
                and s.t1 <= self.window[1]]


def percentile(values, q: float) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}").read


def read_all(run: Run, names) -> Dict[str, float]:
    out = {}
    for name in names:
        v = reader(name)(run)
        if v is not None:
            out[name] = v
    return out
