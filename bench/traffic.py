"""The one traffic generator: reads a mix's data file (`bench/traffic/
<name>.json`) and turns it into requests from `--seed`.

Every seed gets the same work in another order.  Lengths are the
distribution's quantiles at evenly spaced levels, shuffled: the open
loop's window holds one full set of as many levels as it has requests
(the lead-in another), and the closed loop's clients draw from blocks of
one full set per client count.  Open-loop gaps are the arrival law's
quantiles, shuffled; groups and tenants are dealt in fixed shares,
shuffled.  So the count of requests in a window, and the lengths, groups
and tenants behind them, do not change with the seed: only which request
comes when, and the token ids, do.

A mix file holds:
  loop        "open" (arrivals on a schedule) or "closed" (clients that
              send their next request when the last one finishes)
  rate_per_s  open loop: mean arrivals per second
  clients     closed loop: concurrent clients
  lead_in_s   unmeasured traffic before the window (counted in set-up)
  prompt, output   {"median", "sigma", "min", "max"}: a lognormal in
              tokens, clipped
and, optionally:
  arrivals    open loop: {"law": <numpy.random.Generator method>, its
              other arguments}: the law of the gaps between arrivals,
              scaled to the rate; "exponential" (Poisson arrivals, the
              default) or, for bursts, e.g. {"law": "gamma",
              "shape": 0.25} (gaps with a coefficient of variation of 2)
  prefix      {"groups": G, "tokens": L}: requests fall into G groups in
              equal shares, and every prompt of a group starts with the
              group's own L tokens before its own `prompt` tokens (shared
              system prompts or documents: the prefix cache's work)
  tenants     [share, ...]: the share of requests sent to each of the
              configuration's tenants, in its order (default: all to the
              first)
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

HERE = Path(__file__).resolve().parent
CLOSED_PER_CLIENT = 64
LAW_SAMPLE = 1 << 16        # draws behind a law's quantiles


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _levels(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    nd = NormalDist()
    z = np.array([nd.inv_cdf(q) for q in _levels(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def lengths(spec: dict, n: int, block: int,
            rng: np.random.Generator) -> np.ndarray:
    """n lengths: consecutive blocks, each a shuffled full set of `block`
    quantiles."""
    base = lognormal_quantiles(spec, block)
    out = [rng.permutation(base) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n]


def law_quantiles(law: dict, n: int) -> np.ndarray:
    """n quantiles, at evenly spaced levels, of the gap law `law`: exact for
    the exponential, else read from a fixed sample of the law (the same
    for every seed)."""
    kw = {k: v for k, v in law.items() if k != "law"}
    if law["law"] == "exponential" and not kw:
        return -np.log1p(-_levels(n))
    draws = getattr(np.random.default_rng(0), law["law"])(
        size=LAW_SAMPLE, **kw)
    return np.quantile(np.asarray(draws, np.float64), _levels(n))


def open_arrivals(rate: float, span_s: float, rng: np.random.Generator,
                  law: dict) -> np.ndarray:
    """Arrival offsets in [0, span_s): round(rate * span_s) gaps of the law
    at evenly spaced quantile levels, shuffled, scaled to end half a mean
    gap before the span closes."""
    n = max(1, round(rate * span_s))
    gaps = rng.permutation(law_quantiles(law, n))
    t = np.cumsum(gaps)
    return t * (span_s - 0.5 / rate) / t[-1]


def shares(weights, n: int, rng: np.random.Generator) -> np.ndarray:
    """n labels 0..len(weights)-1 in the weights' shares (each label's
    count fixed by the weights), shuffled."""
    cum = np.cumsum(np.asarray(weights, np.float64))
    labels = np.searchsorted(cum / cum[-1], _levels(n))
    return rng.permutation(labels)


@dataclasses.dataclass
class Spec:
    due: float            # seconds after the traffic starts (open loop)
    prompt_len: int       # the request's own prompt tokens
    max_new: int
    window: bool          # due inside the measured window
    seed: int
    index: int
    prefix_len: int = 0   # shared tokens before its own
    group: int = 0        # whose shared prefix
    tenant: int = 0       # index into the configuration's tenants

    def prompt(self, vocab: int, lo: int = 1) -> List[int]:
        """The prompt's token ids: the group's shared prefix, drawn from
        (seed, group), then its own, drawn from (seed, index)."""
        own = np.random.default_rng([self.seed, self.index]).integers(
            lo, vocab, self.prompt_len).tolist()
        if not self.prefix_len:
            return own
        shared = np.random.default_rng([self.seed, self.group, 1 << 20])
        return shared.integers(lo, vocab, self.prefix_len).tolist() + own


def requests(mix: dict, seed: int, window_s: float) -> List[Spec]:
    """Open loop: every request of the lead-in and the window with its due
    time.  Closed loop: the sequence of requests the clients draw from in
    order (due and window are set when a client sends it)."""
    rng = np.random.default_rng(int(seed))
    lead = float(mix["lead_in_s"])
    if mix["loop"] == "open":
        rate = float(mix["rate_per_s"])
        law = mix.get("arrivals", {"law": "exponential"})
        parts = [(0.0, open_arrivals(rate, lead, rng, law), False),
                 (lead, open_arrivals(rate, window_s, rng, law), True)]
        due, plen, olen = [], [], []
        for offset, ts, window in parts:
            due += [(offset + float(t), window) for t in ts]
            plen += list(lengths(mix["prompt"], len(ts), len(ts), rng))
            olen += list(lengths(mix["output"], len(ts), len(ts), rng))
    elif mix["loop"] == "closed":
        clients = int(mix["clients"])
        # far more than the clients can finish in the lead-in and window
        n = clients * CLOSED_PER_CLIENT
        due = [(0.0, False)] * n
        plen = lengths(mix["prompt"], n, clients, rng)
        olen = lengths(mix["output"], n, clients, rng)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n = len(due)
    prefix = mix.get("prefix", {"groups": 1, "tokens": 0})
    groups = (shares([1] * int(prefix["groups"]), n, rng)
              if prefix["tokens"] else np.zeros(n, np.int64))
    tenants = (shares(mix["tenants"], n, rng) if "tenants" in mix
               else np.zeros(n, np.int64))
    return [Spec(due=t, prompt_len=int(p), max_new=int(o), window=w,
                 seed=int(seed), index=i, prefix_len=int(prefix["tokens"]),
                 group=int(g), tenant=int(k))
            for i, ((t, w), p, o, g, k) in enumerate(
                zip(due, plen, olen, groups, tenants))]


def length_range(mix: dict) -> tuple:
    """(shortest prompt, longest prompt + output): the range of sequence
    lengths a (re-)prefill of this mix can see."""
    shared = int(mix.get("prefix", {}).get("tokens", 0))
    return (shared + int(mix["prompt"]["min"]),
            shared + int(mix["prompt"]["max"]) + int(mix["output"]["max"]))
