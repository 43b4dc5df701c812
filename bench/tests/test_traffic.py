"""The generator gives every seed the same work in another order."""
from collections import Counter

import pytest

from bench import traffic

SEEDS = [0, 7, 2**31 + 5, 2**40 + 1]


@pytest.mark.parametrize("name", ["chat", "longprompt"])
def test_open_loop_window_is_the_same_work_for_every_seed(name):
    mix = traffic.load_mix(name)
    runs = [traffic.requests(mix, s, 40.0) for s in SEEDS]
    wins = [[r for r in run if r.window] for run in runs]
    assert len({len(w) for w in wins}) == 1
    assert len(wins[0]) == round(mix["rate_per_s"] * 40.0)
    for key in ("prompt_len", "max_new"):
        sets = [Counter(getattr(r, key) for r in w) for w in wins]
        assert all(s == sets[0] for s in sets)
    lead = mix["lead_in_s"]
    for w in wins:
        dues = [r.due for r in w]
        assert all(lead <= d < lead + 40.0 for d in dues)
        assert dues == sorted(dues)
    # the order and the tokens do change
    assert [r.prompt_len for r in wins[0]] != [r.prompt_len for r in wins[1]]
    assert wins[0][0].prompt(1000) != wins[1][0].prompt(1000)


def test_closed_loop_blocks_hold_every_level_once():
    mix = traffic.load_mix("batch")
    c = mix["clients"]
    for s in SEEDS[:2]:
        reqs = traffic.requests(mix, s, 40.0)
        first = sorted(r.prompt_len for r in reqs[:c])
        second = sorted(r.prompt_len for r in reqs[c:2 * c])
        assert first == second == sorted(
            traffic.lognormal_quantiles(mix["prompt"], c))


def test_lengths_stay_in_their_clip():
    for name in ("chat", "batch", "longprompt"):
        mix = traffic.load_mix(name)
        q = traffic.lognormal_quantiles(mix["prompt"], 1000)
        assert q.min() >= mix["prompt"]["min"]
        assert q.max() <= mix["prompt"]["max"]


def test_prompts_are_the_same_for_the_same_seed():
    mix = traffic.load_mix("chat")
    a = traffic.requests(mix, 2**33, 10.0)
    b = traffic.requests(mix, 2**33, 10.0)
    assert [(r.due, r.prompt(500)) for r in a] == \
        [(r.due, r.prompt(500)) for r in b]


def test_correctness_sample_spreads_over_rows():
    from bench.correctness import Seq, sample

    # 12 finished requests on 4 rows; the longest is on row 0
    fin = [Seq([1] * (50 if i == 5 else 10), [2] * 4, row=i % 4)
           for i in range(12)]
    got = sample(fin, seed=3, target_tokens=8, min_requests=4,
                 max_requests=6)
    assert got[0] is fin[5]
    assert len(got) == 4 and {s.row for s in got} == {0, 1, 2, 3}
    got = sample(fin, seed=3, target_tokens=40, min_requests=4,
                 max_requests=6)
    assert len(got) == 6


def test_bursty_arrivals_keep_the_count_and_spread_the_gaps():
    import numpy as np

    poisson = traffic.load_mix("chat")
    bursty = dict(poisson, arrivals={"law": "gamma", "shape": 0.25})
    cvs = []
    for mix in (poisson, bursty):
        runs = [[r for r in traffic.requests(mix, s, 51.0) if r.window]
                for s in SEEDS]
        assert len({len(w) for w in runs}) == 1
        assert len(runs[0]) == round(mix["rate_per_s"] * 51.0)
        gaps = np.diff([r.due for r in runs[0]])
        cvs.append(gaps.std() / gaps.mean())
    assert 0.7 < cvs[0] < 1.3 and cvs[1] > 1.5


def test_shared_prefixes_and_tenant_shares_are_data():
    mix = dict(traffic.load_mix("chat"), prefix={"groups": 4, "tokens": 300},
               tenants=[0.75, 0.25])
    lo, hi = traffic.length_range(mix)
    assert (lo, hi) == (300 + mix["prompt"]["min"],
                        300 + mix["prompt"]["max"] + mix["output"]["max"])
    for s in SEEDS[:2]:
        reqs = traffic.requests(mix, s, 40.0)
        assert sorted(Counter(r.group for r in reqs).values())[0] >= \
            len(reqs) // 4
        assert Counter(r.tenant for r in reqs)[1] == round(len(reqs) / 4)
        by_group = {}
        for r in reqs:
            p = r.prompt(1000)
            assert len(p) == 300 + r.prompt_len
            by_group.setdefault(r.group, set()).add(tuple(p[:300]))
        assert all(len(v) == 1 for v in by_group.values())
        assert len({next(iter(v)) for v in by_group.values()}) == 4
