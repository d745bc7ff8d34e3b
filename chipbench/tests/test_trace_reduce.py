"""The reduction from the profiler's trace to numbers: its interval arithmetic on
made-up events, and the whole of it on a small trace recorded on the chip
(tests/record_trace.py: three jobs of one jitted loop, 20 ms asleep after each)."""
import os

import pytest

from chipbench import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")


def test_merge_clip_total():
    merged = tr.merge([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)])
    assert merged == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.total(tr.clip(merged, 1.0, 3.5)) == pytest.approx(1.5)
    assert tr.busy_inside(merged, 10.0, 11.0) == 0.0


def test_self_time_does_not_count_a_loops_body_twice():
    events = [("while", 0.0, 10.0), ("fusion", 1.0, 4.0), ("fusion", 5.0, 9.0), ("copy", 11.0, 12.0)]
    own = tr.self_times(events)
    assert own["while"] == pytest.approx(3.0) and own["fusion"] == pytest.approx(7.0)
    assert sum(own.values()) == pytest.approx(11.0)    # the union's length


@pytest.fixture(scope="module")
def summary():
    return tr.summarize(tr.load(TRACE), 1)


def test_recorded_trace_busy_idle_and_spans(summary):
    s = summary
    assert 0 < s["busy_s"] < s["window_s"]
    assert len(s["spans"]["job"]) == 3 and len(s["spans"]["between-jobs"]) == 3
    asleep = sum(e - b for b, e in s["spans"]["between-jobs"])
    assert asleep >= 0.06
    assert s["window_s"] - s["busy_s"] >= 0.9 * asleep       # the device idles while the host sleeps
    # device time per operation adds up to the busy time (self times, one chip)
    assert sum(sec for _, sec in s["device_ops"]) == pytest.approx(s["busy_s"], rel=1e-6)


def test_recorded_trace_gaps_are_named_by_the_host_span(summary):
    longest = summary["idle_gaps"][:3]
    assert [name for name, _ in longest] == ["between-jobs"] * 3
    assert all(sec >= 0.015 for _, sec in longest)


def test_recorded_trace_jobs_keep_the_device_busy(summary):
    for b, e in summary["spans"]["job"]:
        assert tr.busy_inside(summary["busy_intervals"], b, e) > 0.5 * (e - b)
    assert any("work" in name for name in summary["modules"])
