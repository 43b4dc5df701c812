"""The comparison that decides `correct`: served tokens against the plain
reference.

Once the window has closed and the engine is freed, a sample of the
requests the engine finished (the longest among them, then others drawn
from the seed over as many decode rows as it can, until `sample_tokens`
served tokens and `min_requests` requests) is run through the
configuration's float32 reference (`bench/references/<name>.py`) over its
prompt and served tokens.  At every served position the gap is the
reference's best logit less the reference's logit of the token the engine
served: 0 where the engine served the reference's choice, small where
bf16 rounding flipped a near tie.  Two numbers are compared: the widest
gap, which catches a token that is plainly wrong, and the mean gap, which
catches many small flips (a precision drop moves many near ties at once).

The control (`mode`: the reference itself at int8 or fp8) reads, at the
same positions of the same sequences, the gap of the token that the lower
precision puts first, and is judged by the same `judge`.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Seq:
    prompt: List[int]
    served: List[int]
    row: Optional[int] = None     # the decode row it ran in


def sample(finished: List[Seq], seed: int, target_tokens: int,
           min_requests: int, max_requests: int) -> List[Seq]:
    """The longest finished request, then others in an order drawn from
    the seed, each from a decode row not yet in the sample while there is
    one, until at least `target_tokens` served tokens and `min_requests`
    requests (at most `max_requests`).  Spreading the sample over the
    rows is what lets a fault confined to some rows of the batch show."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i].prompt)
                                   + len(finished[i].served)))
    rng = np.random.default_rng([int(seed), 2])
    rest = [order[1:][i] for i in rng.permutation(len(order) - 1)]
    picked, rows = [order[0]], {finished[order[0]].row}
    for i in rest:                      # one request per new row first
        if finished[i].row not in rows:
            picked.append(i)
            rows.add(finished[i].row)
    chosen = set(picked)
    picked += [i for i in rest if i not in chosen]
    out, n = [], 0
    for i in picked:
        if ((n >= target_tokens and len(out) >= min_requests)
                or len(out) >= max_requests):
            break
        out.append(finished[i])
        n += len(finished[i].served)
    return out


@jax.jit
def _gaps(ref, tok):
    """Reference's best logit less its logit of `tok`, per row."""
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]


def reference_module(conf: dict):
    return importlib.import_module(f"bench.references.{conf['reference']}")


def gaps(w: dict, conf: dict, seqs: List[Seq], mode: Optional[str] = None
         ) -> np.ndarray:
    """Per served position: the reference's gap of the served token, or,
    with `mode`, of the token the `mode` control puts first."""
    ref_mod = reference_module(conf)
    hf = conf["config"]
    out = []
    with jax.default_matmul_precision("highest"):
        for s in seqs:
            tokens = list(s.prompt) + list(s.served[:-1])
            positions = np.arange(len(s.prompt) - 1, len(tokens))
            ref = ref_mod.logits(w, hf, tokens, positions)
            if mode is None:
                tok = jnp.asarray(np.asarray(s.served, np.int32))
            else:
                ctl = ref_mod.logits(w, hf, tokens, positions, mode=mode)
                tok = jnp.argmax(ctl, axis=-1).astype(jnp.int32)
                del ctl
            out.append(np.asarray(_gaps(ref, tok)))
            del ref
    return np.concatenate(out) if out else np.zeros(0)


def judge(gap: np.ndarray, cc: dict, *, bad: int, rejected: int,
          compiles_in_window: int):
    """(correct, checks): every number compared with its limit.  `gap` is
    the program's (or a control's) gap at each compared position; `cc` is
    the configuration's `correctness` block."""
    n = int(gap.size)
    stat = {"gap_max": float(gap.max()) if n else None,
            "gap_mean": float(gap.mean()) if n else None}
    checks = {
        "served_tokens_min": {"value": n, "limit": cc["sample_tokens"] // 4},
        "bad_finished": {"value": bad, "limit": 0},
        "rejected": {"value": rejected, "limit": 0},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
    }
    for k, v in stat.items():
        checks[k] = {"value": v, "limit": float(cc["limits"][k])}
    correct = (n >= max(1, cc["sample_tokens"] // 4) and not bad
               and not rejected and not compiles_in_window
               and all(stat[k] <= checks[k]["limit"] for k in stat))
    return bool(correct), checks
