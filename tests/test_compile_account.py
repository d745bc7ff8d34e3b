#
# The compile account (profiling.watch_compiles / compile_events /
# compile_summary): jax's own trace, lowering and backend events kept as
# compile.* counters, a bounded journal and a by-name roll-up, with tracing
# off; a thread's outermost event of each kind is what counts.  Synthetic
# events go through the listeners themselves; the fits go through the public
# API, at shapes no other test of the process compiles.
#
import glob
import os
import threading

import numpy as np
import pytest

from spark_rapids_ml_tpu import profiling
from spark_rapids_ml_tpu.ops import precompile

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def account():
    precompile.ensure_compile_cache()
    profiling.reset_compile_account()
    yield profiling
    profiling.reset_compile_account()


def _feed(event, start, end, name, inside=(), thread=None):
    """One event as jax reports it: its opening, what opens inside it, its close."""
    if thread is not None:
        t = threading.Thread(target=_feed, args=(event, start, end, name, inside), name=thread)
        t.start()
        t.join()
        return
    profiling._on_compile_open(event, start, fun_name=name)
    for child in inside:
        _feed(event, *child)
    profiling._on_compile_span(event, start, end, fun_name=name)


def test_ensure_compile_cache_registers_the_listeners_once(account):
    from jax._src import monitoring

    precompile.ensure_compile_cache()
    profiling.watch_compiles()
    assert monitoring.get_scalar_listeners().count(profiling._on_compile_open) == 1
    assert monitoring.get_event_time_span_listeners().count(profiling._on_compile_span) == 1
    assert monitoring.get_event_listeners().count(profiling._on_compile_event) == 1


@pytest.mark.parametrize(
    "events,trace_us,names",
    [
        # jax reports the callee's trace inside the caller's
        ([(0.5, 3.0, "f", [(1.0, 2.0, "matmul")])], 2_500_000, {"f": (1, 2.5)}),
        # two callees, one of them with a callee of its own
        (
            [(0.5, 3.0, "f", [(0.75, 1.5, "g", [(1.0, 1.25, "tanh")]), (2.0, 2.5, "matmul")])],
            2_500_000,
            {"f": (1, 2.5)},
        ),
        # disjoint events add up; the same name twice counts twice
        ([(1.0, 2.0, "f"), (2.0, 2.5, "f"), (4.0, 4.25, "g")], 1_750_000, {"f": (2, 1.5), "g": (1, 0.25)}),
    ],
    ids=["one_nested", "two_levels", "disjoint"],
)
def test_a_nested_trace_is_counted_once(account, events, trace_us, names):
    for event in events:
        _feed(TRACE, *event)
    assert account.counter("compile.trace_us") == trace_us
    summary = account.compile_summary()
    assert {k: (v["trace"]["count"], v["trace"]["total_s"]) for k, v in summary.items()} == names
    # the journal keeps what the counters count: a thread's outermost events
    assert [(e[1], e[2], e[3]) for e in account.compile_events()] == [(e[2], e[0], e[1]) for e in events]


def test_an_event_opened_before_the_listeners_counts_as_outermost(account):
    profiling._on_compile_span(TRACE, 1.0, 2.0, fun_name="f")       # no opening seen
    _feed(TRACE, 3.0, 4.0, "g", [(3.25, 3.5, "tanh")])
    assert account.counter("compile.trace_us") == 2_000_000
    assert sorted(account.compile_summary()) == ["f", "g"]


def test_kinds_nest_apart_and_lowerings_drop_the_jit_wrapper(account):
    _feed(TRACE, 1.0, 2.0, "f")
    _feed(LOWER, 2.0, 2.5, "jit(f)")
    _feed(BACKEND, 0.5, 3.0, "jit(f)")      # contains both, and takes nothing from them
    assert account.counters("compile.") == {
        "compile.trace_us": 1_000_000, "compile.lower_us": 500_000,
        "compile.backend_us": 2_500_000, "compile.executables": 1,
    }
    assert account.compile_summary() == {
        "f": {
            "trace": {"count": 1, "total_s": 1.0},
            "lower": {"count": 1, "total_s": 0.5},
            "backend": {"count": 1, "total_s": 2.5},
        }
    }
    assert [e[0] for e in account.compile_events()] == ["trace", "lower", "backend"]


def test_threads_add_up_as_thread_seconds_and_keep_their_names(account):
    _feed(LOWER, 10.0, 12.0, "jit(a)", thread="srml-precompile-3")
    _feed(LOWER, 11.0, 13.0, "jit(b)", thread="srml-precompile-4")
    # overlapping in wall time, on two threads: neither is nested in the other
    assert account.counter("compile.lower_us") == 4_000_000
    assert [(e[1], e[4]) for e in account.compile_events()] == [
        ("a", "srml-precompile-3"), ("b", "srml-precompile-4"),
    ]


def test_the_pools_workers_land_in_the_journal_under_their_names(account):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def account_pool_probe(x):
        return jnp.tanh(x @ x.T).sum()

    pool = precompile.Precompiler(max_workers=2)
    pool.submit("probe", account_pool_probe, precompile.aval((7, 5), jnp.float32))
    pool.wait(["probe"])
    mine = [e for e in account.compile_events() if e[1] == "account_pool_probe"]
    assert {e[0] for e in mine} == {"trace", "lower", "backend"}
    assert all(e[4].startswith("srml-precompile-") for e in mine)
    assert all(e[3] >= e[2] > 1e9 for e in mine), "start and end are time.time() readings"
    # tanh and the product were traced inside the probe's trace: one interval
    trace = account.compile_summary()["account_pool_probe"]["trace"]
    assert trace["count"] == 1
    assert account.counter("compile.trace_us") == pytest.approx(1e6 * trace["total_s"], abs=2)
    assert account.counter("compile.executables") == 1


def test_the_journal_is_bounded_and_the_roll_up_is_not(account, monkeypatch):
    monkeypatch.setattr(profiling, "_EVENT_CAP", 8)
    for i in range(20):
        _feed(BACKEND, float(i), i + 0.5, f"jit(fn{i})")
    assert len(account.compile_events()) == 8
    assert [e[1] for e in account.compile_events()] == [f"fn{i}" for i in range(8)]
    assert len(account.compile_summary()) == 20
    assert account.counter("compile.executables") == 20
    assert account.counter("compile.backend_us") == 10_000_000


def test_cache_events_are_counted_and_others_ignored(account):
    profiling._on_compile_event("/jax/compilation_cache/cache_hits")
    profiling._on_compile_event("/jax/compilation_cache/cache_hits")
    profiling._on_compile_event("/jax/compilation_cache/cache_misses")
    profiling._on_compile_event("/jax/compilation_cache/compile_requests_use_cache")
    _feed("/jax/some/other_duration", 1.0, 2.0, "f")
    assert account.counters("compile.") == {"compile.cache_hits": 2, "compile.cache_misses": 1}
    assert account.compile_events() == []


def test_a_first_fit_shows_what_it_built_and_a_second_fit_nothing(account):
    from spark_rapids_ml_tpu import KMeans
    from spark_rapids_ml_tpu.dataframe import DataFrame

    X = np.random.default_rng(34).standard_normal((331, 13)).astype(np.float32)
    df = DataFrame.from_numpy(X, feature_layout="array", num_partitions=1)
    est = KMeans(k=5, maxIter=3, seed=34, num_workers=1)
    first = est.fit(df).fit_telemetry().counters
    assert first["compile.trace_us"] > 0 and first["compile.lower_us"] > 0
    assert first["compile.backend_us"] > 0 and first["compile.executables"] > 0
    second = est.fit(df).fit_telemetry().counters
    assert {k: v for k, v in second.items() if k.startswith("compile.")} == {}
    # the process-wide surface carries them too, and by name
    exported = profiling.export_metrics("compile.")["counters"]
    assert exported["compile.executables"] == first["compile.executables"]
    assert 'srml_counter{name="compile.trace_us"}' in profiling.render_prometheus()
    assert "lloyd_iterations" in account.compile_summary()


def test_the_package_times_its_own_import():
    import spark_rapids_ml_tpu

    before = profiling.counter("import.us")
    assert before > 0
    spark_rapids_ml_tpu.KMeans      # loaded long ago: a few microseconds, counted
    assert 0 <= profiling.counter("import.us") - before < 100_000


def test_no_precompile_log_switch_is_left_in_the_package():
    for path in glob.glob(os.path.join(ROOT, "spark_rapids_ml_tpu", "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            assert "SRML_PRECOMPILE_LOG" not in f.read(), path
