"""The cell's system under test and the loop that drives it.

`build_engine` makes a `ServingEngine` with the tenants of a
configuration file (its `ModelConfig` is the configuration's family's,
`bench/families`); `warm_up` runs one request of every prefill shape the
mix can reach; `Driver` offers the mix's traffic (open or closed loop), calls
`eng.step()`, and stamps each token at the moment the step that made it
visible returns.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

from bench import traffic

HERE = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def tenants(conf: dict) -> List[str]:
    """The configuration's tenant names; each serves the seed's weights."""
    return list(conf["engine"]["tenants"])


def build_engine(conf: dict, model_cfg, params, tracer=None):
    """The configuration's tenants on the engine's normal path: paged KV,
    the configured decode backend, chunked prefill under a token budget,
    fused greedy sampling; every tenant's layers stay resident."""
    from repro.serving import (EngineModel, SchedulerConfig, ServingEngine)

    e = conf["engine"]
    models = [EngineModel(name, params, model_cfg,
                          kv_slots=e["decode_rows"], kv_layout="paged",
                          page_size=e["page_size"], n_pages=e["kv_pages"],
                          kernel_backend=e["kernel_backend"])
              for name in tenants(conf)]
    return ServingEngine(
        models, tracer=tracer,
        sched=SchedulerConfig(
            max_queue=1 << 20,
            max_prefill_per_step=e["max_prefill_per_step"],
            prefill_token_budget=e["prefill_token_budget"]),
        prefill_chunk=e["prefill_chunk"], bucket_min=e["bucket_min"],
        bucket_growth=e["bucket_growth"],
        staging_growth=e["staging_growth"])


def warm_lengths(eng, conf: dict, mix: dict) -> List[int]:
    """One prompt length for each distinct (staging rung, tail bucket,
    has full chunks) that a (re-)prefill of this mix can reach: together
    they compile every chunk-prefill program, page write and staging
    allocation the window can call."""
    from repro.serving.bucketing import bucket_for, bucket_ladder

    e = conf["engine"]
    chunk = e["prefill_chunk"]
    ladder = bucket_ladder(min(e["bucket_min"], chunk), chunk,
                           e["bucket_growth"])
    lo, hi = traffic.length_range(mix)
    hi = min(hi, e["kv_pages"] * e["page_size"])
    reps: Dict[tuple, int] = {}
    for n in range(lo, hi + 1):
        rung = eng.staging_len_for(tenants(conf)[0], n)
        tail = n - (math.ceil(n / chunk) - 1) * chunk
        padded = min(bucket_for(tail, ladder), rung - (n - tail))
        reps.setdefault((rung, padded, n > chunk), n)
    return sorted(reps.values())


def warm_up(eng, conf: dict, lengths: List[int], vocab: int) -> None:
    """Serve one greedy request of each length in turn to each tenant, two
    tokens each (the prefill's and one decode step's): one staging buffer
    at a time."""
    for name in tenants(conf):
        for i, n in enumerate(lengths):
            eng.submit(name, [1 + (7 * i + j) % (vocab - 1)
                              for j in range(n)], max_new_tokens=2)
            eng.run()


@dataclasses.dataclass
class Tracked:
    spec: traffic.Spec
    req: object             # the engine's Request
    due: float              # clock time the request was due
    sent: float             # clock time it was submitted
    window: bool            # due inside the measured window
    client: Optional[int]
    stamps: List[float] = dataclasses.field(default_factory=list)
    done: bool = False
    row: Optional[int] = None   # the decode row it first ran in


@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    decode_positions: List[int]     # position written by each decoded row
    prefill_tokens: int
    chunks: List[tuple]             # (start, tokens) of each prefill chunk
    components: Dict[str, float]    # the engine tracer's seconds by part
    traced: bool = False


class Driver:
    """Offers a mix to one engine and keeps what the metrics read."""

    def __init__(self, eng, mix: dict, specs: List[traffic.Spec],
                 vocab: int, tenants: List[str],
                 clock: Callable[[], float] = time.perf_counter,
                 annotate: Optional[Callable] = None):
        self.eng, self.mix, self.specs, self.vocab = eng, mix, specs, vocab
        self.tenants = tenants
        self.clock = clock
        self.annotate = annotate
        self.tracked: List[Tracked] = []
        self.live: List[Tracked] = []
        self.steps: List[StepRec] = []
        self.tracing = False
        self._tracer_cursor = 0

    def _span(self, name: str):
        if self.annotate is None:
            return contextlib.nullcontext()
        return self.annotate("bench." + name)

    # ------------------------------------------------------------ traffic
    def _send(self, spec: traffic.Spec, due: float, window: bool,
              client: Optional[int]) -> None:
        prompt = spec.prompt(self.vocab)
        with self._span("submit"):
            req = self.eng.submit(self.tenants[spec.tenant], prompt,
                                  max_new_tokens=spec.max_new, arrival_t=due)
        t = Tracked(spec, req, due, self.clock(), window, client)
        self.tracked.append(t)
        self.live.append(t)

    def _stamp(self, now: float) -> List[Tracked]:
        """Give each token made visible by the last step the time the step
        returned; returns the requests that finished."""
        finished, still = [], []
        for t in self.live:
            n = len(t.req.generated)
            if n > len(t.stamps):
                t.stamps.extend([now] * (n - len(t.stamps)))
            if t.row is None and t.req.slot is not None:
                t.row = t.req.slot
            status = t.req.status.name
            if status in ("FINISHED", "REJECTED"):
                t.done = True
                finished.append(t)
            else:
                still.append(t)
        self.live = still
        return finished

    def _decode_positions(self, before: Dict[int, int]) -> List[int]:
        """Rows the last step decoded, by the position each wrote: a
        request that had k >= 1 tokens and now has more decoded one, as
        did one that went from 0 to 2 (prefill's token and a decode's)."""
        out = []
        for t in self.live:
            had = before.get(id(t), 0)
            n = len(t.req.generated)
            if n > had and (had >= 1 or n - had >= 2):
                out.append(len(t.req.prompt) + n - 2)
        return out

    def _chunks(self) -> List[tuple]:
        tr = self.eng.tracer
        if not getattr(tr, "enabled", False):
            return []
        self._tracer_cursor, new = tr.events_since(self._tracer_cursor)
        return [(ev["args"]["start"], ev["args"]["tokens"]) for ev in new
                if ev.get("name") == "prefill_chunk"]

    def step(self) -> List[Tracked]:
        before = {id(t): len(t.req.generated) for t in self.live}
        t0 = self.clock()
        with self._span("step"):
            self.eng.step()
        t1 = self.clock()
        rec = self.eng.metrics.steps[-1]
        positions = self._decode_positions(before)
        self.steps.append(StepRec(t0, t1, positions, rec.prefill_tokens,
                                  self._chunks(), dict(rec.component_s),
                                  self.tracing))
        return self._stamp(t1)

    def run(self, start: float, window_s: float,
            on_boundary: Optional[Callable[[float], None]] = None) -> None:
        """Offer the lead-in and the window from `start`; returns at the
        first step boundary at or after the window's end.
        `on_boundary(now)` is called between steps (the profiler starts and
        stops there)."""
        lead = float(self.mix["lead_in_s"])
        w0, w1 = start + lead, start + lead + window_s
        self.window = (w0, w1)
        closed = self.mix["loop"] == "closed"
        if closed:
            n_clients = int(self.mix["clients"])
            pending = [(start + j * lead / (2 * n_clients), j)
                       for j in range(n_clients)]
            order = iter(self.specs)
        else:
            pending = [(start + s.due, i) for i, s in enumerate(self.specs)]
        pending.sort()
        cursor = 0
        while True:
            now = self.clock()
            if now >= w1:
                break
            if on_boundary is not None:
                on_boundary(now)
            while cursor < len(pending) and pending[cursor][0] <= now:
                due, k = pending[cursor]
                cursor += 1
                if closed:
                    self._send(next(order), due, w0 <= due < w1, k)
                else:
                    spec = self.specs[k]
                    self._send(spec, due, spec.window, None)
            if self.eng.has_work():
                finished = self.step()
                if closed:
                    for t in finished:
                        pending.append((t.stamps[-1] if t.stamps
                                        else self.clock(), t.client))
                    pending[cursor:] = sorted(pending[cursor:])
            else:
                nxt = pending[cursor][0] if cursor < len(pending) else w1
                with self._span("wait_arrival"):
                    time.sleep(max(0.0, min(nxt, w1) - self.clock()))
