"""The trace reduction on a small profiler trace recorded on the CPU
(`bench/testdata/make_cpu_trace.py`): three jitted matmul steps inside
`bench.step`, separated by 20 ms sleeps inside `bench.wait_arrival`."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "cpu_trace.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.load(str(TRACE))


def test_union_merges_overlaps_and_clips():
    ivs = [(0, 10), (5, 20), (30, 40), (39, 41), (50, 60)]
    assert tr.union(ivs) == [(0, 20), (30, 41), (50, 60)]
    assert tr.clip(tr.union(ivs), 15, 55) == [(15, 20), (30, 41), (50, 55)]


def test_busy_is_the_union_of_device_ops(trace):
    lo, hi = tr.annotation_span(trace, "bench.step")
    ops = [(e.start, e.end) for e in trace.device_ops]
    assert len(ops) == 6
    want = sum(e - s for s, e in tr.union(tr.clip(ops, lo, hi)))
    assert tr.busy_ns(trace, lo, hi) == pytest.approx(want)
    # the three 20 ms sleeps are idle: busy is a small part of the window
    assert 0 < tr.busy_ns(trace, lo, hi) < 0.2 * (hi - lo)


def test_kernel_time_by_name(trace):
    lo, hi = tr.annotation_span(trace, "bench.step")
    dots = sum(e.end - e.start for e in trace.device_ops
               if e.name.startswith("dot_general"))
    assert dots > 0
    assert tr.kernel_ns(trace, r"dot_general", lo, hi) == pytest.approx(dots)
    assert tr.kernel_ns(trace, r"no_such_kernel", lo, hi) == 0
    assert tr.top_ops(trace, lo, hi)[0][0].startswith("dot_general")


def test_idle_gaps_are_attributed_to_host_annotations(trace):
    lo, hi = tr.annotation_span(trace, "bench.step")
    gaps = tr.idle_gaps(trace, lo, hi, n=3)
    assert len(gaps) == 3
    # the two sleeps inside the window are the longest gaps, ~20 ms each
    for label, seconds in gaps[:2]:
        assert label.startswith("bench.wait_arrival")
        assert 0.015 < seconds < 0.05
    assert gaps[0][1] >= gaps[1][1] >= gaps[2][1]
    assert not gaps[2][0].startswith("bench.wait_arrival")
