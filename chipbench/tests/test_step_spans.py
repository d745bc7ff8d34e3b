"""The readers of the program's step spans: their arithmetic on a made-up
summary, the whole of them on a small trace recorded on the chip
(tests/record_step_trace.py: a few public KMeans fits at a test's size), and
what they return where there is nothing to read."""
import os
from types import SimpleNamespace

import pytest

from chipbench import harness, step_spans
from chipbench import trace_reduce as tr

from .conftest import small
from .test_rehearsal import _run

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "steps.xplane.pb")
STEPS = ("api", "ingest", "launch", "result")
NEW = [
    m["name"] for m in harness.load_benchmark()["per_layer"]
    if "_idle_ms_per_job" in m["name"] or m["name"].startswith(("lbfgs.evals", "trace.device_lead"))
]


def _read(name, trace, detail=None):
    return harness.load_reader(name).read(SimpleNamespace(trace=trace, detail=detail))


def _made_up():
    """Two jobs of 100 ms: the device runs from 12 to 80 ms of each on its own
    clock, which leads the host's by 1 ms."""
    ms = 1e-3
    steps = {
        "job": (0, 100), "srml.prepare": (0, 4), "srml.ingest": (4, 6), "srml.fit": (6, 96),
        "srml.finish": (96, 100), "srml.fit.init": (6, 10), "srml.fit.solve": (10, 12),
        "srml.fit.wait": (12, 79.5), "srml.fit.fetch": (79.5, 90), "srml.fit.pack": (90, 96),
    }
    spans = {n: [((a + at) * ms, (b + at) * ms) for at in (0, 100)] for n, (a, b) in steps.items()}
    busy = [((a + at) * ms, (b + at) * ms) for at in (0, 100) for a, b in ((12, 40), (41, 80))]
    return {"spans": spans, "busy_intervals": busy}


def test_idle_inside_named_spans_on_a_made_up_summary():
    s = _made_up()
    assert _read("fit.api_idle_ms_per_job", s) == pytest.approx(8.0)
    assert _read("fit.ingest_idle_ms_per_job", s) == pytest.approx(2.0)
    assert _read("fit.launch_idle_ms_per_job", s) == pytest.approx(6.0 + 1.0)     # before 12 ms; the hole at 40
    assert _read("fit.result_idle_ms_per_job", s) == pytest.approx(16.5 - 0.5)    # the device's last half ms
    assert sum(_read(f"fit.{k}_idle_ms_per_job", s) for k in STEPS) == pytest.approx(_read("fit.host_ms_per_job", s))
    detail = {}
    assert _read("trace.device_lead_ms", s, detail) == pytest.approx(0.5)         # 80 - 79.5: at most the lead
    assert detail["device_lead_ms"]["upper"] == pytest.approx(6.0)                # 112 - 106: at least the lead


def test_device_lead_matches_jobs_by_order_where_the_offset_moves_work_across_them():
    """A device clock 14 ms behind puts the second job's first operations inside
    the first job's span: the idle stretch between the jobs is still theirs."""
    s = _made_up()
    s["busy_intervals"] = [(a - 14e-3, b - 14e-3) for a, b in s["busy_intervals"]]
    detail = {}
    assert _read("trace.device_lead_ms", s, detail) == pytest.approx(0.5 - 14.0)
    assert detail["device_lead_ms"]["upper"] == pytest.approx(6.0 - 14.0)
    one_job = {"spans": {n: v[:1] for n, v in s["spans"].items()}, "busy_intervals": s["busy_intervals"][:2]}
    assert _read("trace.device_lead_ms", one_job, {}) is None      # nothing lies between one job


def test_twins_read_what_the_plain_readers_read():
    s = _made_up()
    for name in NEW:
        if name.endswith(".lbfgs"):
            assert _read(name, s, {}) == _read(name[: -len(".lbfgs")], s, {})


@pytest.mark.parametrize("name", [n for n in NEW if n != "lbfgs.evals_per_job"])
def test_new_readers_return_nothing_without_a_trace_or_without_the_spans(name):
    assert _read(name, None) is None
    bare = {"spans": {"job": [(0.0, 1.0)], "srml.fit": [(0.1, 0.9)]}, "busy_intervals": [(0.2, 0.8)]}
    assert _read(name, bare) is None      # a program from before the step spans


@pytest.fixture(scope="module")
def summary():
    return tr.summarize(tr.load(TRACE), 1)


def test_recorded_trace_carries_every_step_of_every_job(summary):
    jobs = summary["spans"]["job"]
    assert len(jobs) >= 2
    for name in ("srml.prepare", "srml.ingest", "srml.fit", "srml.finish", "srml.fit.init",
                 "srml.fit.solve", "srml.fit.wait", "srml.fit.fetch", "srml.fit.pack"):
        assert len(summary["spans"][name]) == len(jobs), name
    # the program's spans cover every job span: what is left is the driver's own
    for s, e in jobs:
        top = sum(b - a for n in ("srml.prepare", "srml.ingest", "srml.fit", "srml.finish")
                  for a, b in summary["spans"][n] if s <= a and b <= e)
        assert 0 <= (e - s) - top < 0.3e-3


def test_recorded_trace_steps_add_up_to_the_host_time_of_a_job(summary):
    steps = [_read(f"fit.{k}_idle_ms_per_job", summary) for k in STEPS]
    host = _read("fit.host_ms_per_job", summary)
    assert all(v is not None and v >= 0 for v in steps)
    assert sum(steps) <= host and host - sum(steps) <= max(0.05 * host, 0.5)
    # no gap of a job is left to the driver's span or to srml.fit as a whole
    inside_jobs = [name for name, sec in summary["idle_gaps"] if sec > 20e-6 and name not in ("between-jobs", "window")]
    assert inside_jobs and not {"job", "srml.fit"} & set(inside_jobs)


def test_recorded_trace_device_lead_reads_a_planted_offset(summary):
    detail = {}
    lower = _read("trace.device_lead_ms", summary, detail)
    assert lower <= detail["device_lead_ms"]["upper"]
    planted = dict(summary, busy_intervals=[(s + 4e-3, e + 4e-3) for s, e in summary["busy_intervals"]])
    moved = {}
    assert _read("trace.device_lead_ms", planted, moved) == pytest.approx(lower + 4.0, abs=1e-6)
    assert moved["device_lead_ms"]["upper"] == pytest.approx(detail["device_lead_ms"]["upper"] + 4.0, abs=1e-6)


def test_rehearsal_counts_evaluations_and_leaves_the_span_metrics_out():
    """The CPU rehearsal has no device trace: of the new metrics only the
    program's evaluation count is there to read."""
    result, _ = _run("logreg_fit", trace=True)
    reported = set(result["metrics"]) & set(NEW)
    assert reported == {"lbfgs.evals_per_job"}
    iters = small("logreg_fit")["params"]["maxIter"]
    assert result["metrics"]["lbfgs.evals_per_job"]["value"] >= iters + 1
    result, _ = _run("kmeans_fit", trace=True)
    assert not set(result["metrics"]) & set(NEW)
