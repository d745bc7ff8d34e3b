# graftlint rule tests: every rule R1-R5 must FIRE on a minimal bad snippet
# and stay SILENT on the corrected version, pragmas must suppress, the
# baseline must demote, and the real tree must lint clean (the zero-findings
# gate that keeps the pass trustworthy — a linter the tree itself violates
# trains everyone to ignore it).
import os
import textwrap

import pytest

from tools.graftlint import (
    apply_baseline,
    collect_pragmas,
    lint_paths,
    lint_source,
    load_baseline,
    write_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(src: str, path: str = "pkg/mod.py", rules=None):
    return lint_source(textwrap.dedent(src), path=path, rules=rules)


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# -- R1: host sync in hot path ------------------------------------------------

R1_BAD_LOOP = """
    import jax
    import jax.numpy as jnp

    def fit(a, n):
        total = 0.0
        for i in range(n):
            x = jnp.sum(a) * i
            total += float(x)
        return total
"""

R1_GOOD_LOOP = """
    import jax
    import jax.numpy as jnp

    def fit(a, n):
        parts = []
        for i in range(n):
            parts.append(jnp.sum(a) * i)
        return sum(float(v) for v in jax.device_get(parts))
"""


def test_r1_fires_on_float_in_loop():
    findings = _lint(R1_BAD_LOOP)
    assert _rules_of(findings) == ["R1"]
    assert "device->host" in findings[0].message


def test_r1_silent_on_batched_fetch():
    assert _lint(R1_GOOD_LOOP) == []


def test_r1_fires_on_asarray_in_jitted_body():
    findings = _lint(
        """
        import numpy as np
        import jax
        import jax.numpy as jnp

        @jax.jit
        def kernel(x):
            y = jnp.sum(x)
            return np.asarray(y)
        """
    )
    assert "R1" in _rules_of(findings)


def test_r1_fires_on_device_get_inside_loop():
    findings = _lint(
        """
        import jax
        import jax.numpy as jnp

        def fit(a, n):
            out = []
            for i in range(n):
                out.append(jax.device_get(jnp.sum(a) * i))
            return out
        """
    )
    assert _rules_of(findings) == ["R1"]


def test_r1_untaints_through_shape_and_range():
    # vals.shape[0] / range() yield host ints: the loop variable must not
    # count as device data (regression: r taint via `range(vals.shape[0])`)
    assert (
        _lint(
            """
            import numpy as np
            import jax
            import jax.numpy as jnp

            def check(vals):
                ai = jnp.argsort(vals)
                ai_h = jax.device_get(ai)
                for r in range(vals.shape[0]):
                    print(ai_h[r].tolist())
            """
        )
        == []
    )


def test_r1_ignores_plain_numpy_loops():
    assert (
        _lint(
            """
            import numpy as np

            def ingest(parts):
                out = []
                for p in parts:
                    out.append(np.asarray(p, dtype=np.float32))
                return np.concatenate(out)
            """
        )
        == []
    )


# -- R2: recompile risk -------------------------------------------------------

R2_BAD_PARAM = """
    import jax

    @jax.jit
    def solve(x, n_iter):
        return x * n_iter
"""

R2_GOOD_PARAM = """
    from functools import partial
    import jax

    @partial(jax.jit, static_argnames=("n_iter",))
    def solve(x, n_iter):
        return x * n_iter
"""


def test_r2_fires_on_unmarked_shape_param():
    findings = _lint(R2_BAD_PARAM)
    assert _rules_of(findings) == ["R2"]
    assert "static_argnames" in findings[0].message


def test_r2_silent_with_static_argnames():
    assert _lint(R2_GOOD_PARAM) == []


def test_r2_fires_on_python_if_over_tracer():
    findings = _lint(
        """
        import jax

        @jax.jit
        def pick(x, flag):
            if flag:
                return x
            return -x
        """
    )
    assert _rules_of(findings) == ["R2"]
    assert "lax.cond" in findings[0].message


def test_r2_allows_static_shape_and_structure_tests():
    assert (
        _lint(
            """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def pad(q, items):
                if q.shape[1] != items.shape[1]:
                    q = jnp.pad(q, ((0, 0), (0, items.shape[1] - q.shape[1])))
                if q.ndim == 1:
                    q = q[None, :]
                return q
            """
        )
        == []
    )


# -- R3: axis names bound through parallel/mesh -------------------------------


def test_r3_fires_on_string_literal_axis():
    findings = _lint(
        """
        import jax

        def agg(x):
            return jax.lax.psum(x, "data")
        """
    )
    assert _rules_of(findings) == ["R3"]
    assert "parallel/mesh" in findings[0].message


def test_r3_fires_on_module_local_axis_string():
    findings = _lint(
        """
        import jax

        AXIS = "data"

        def agg(x):
            return jax.lax.psum(x, AXIS)
        """
    )
    assert _rules_of(findings) == ["R3"]


def test_r3_counts_nested_constructor_literal_once():
    # P("data") nested in NamedSharding must be ONE finding, not two — a
    # double count would also corrupt --baseline budgets
    findings = _lint(
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        def shard(mesh):
            return NamedSharding(mesh, P("data"))
        """
    )
    assert _rules_of(findings) == ["R3"]
    assert len(findings) == 1


def test_r3_silent_on_mesh_bound_axis():
    assert (
        _lint(
            """
            import jax
            from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

            def agg(x):
                return jax.lax.psum(x, DATA_AXIS)
            """
        )
        == []
    )


def test_r3_fires_on_partition_spec_literal():
    findings = _lint(
        """
        from jax.sharding import PartitionSpec as P

        def spec():
            return P("data")
        """
    )
    assert _rules_of(findings) == ["R3"]


# -- R4: nondeterminism -------------------------------------------------------


def test_r4_fires_on_legacy_global_rng():
    findings = _lint(
        """
        import numpy as np

        def sample(n):
            return np.random.normal(size=n)
        """
    )
    assert _rules_of(findings) == ["R4"]
    assert "GLOBAL RNG" in findings[0].message


def test_r4_fires_on_unseeded_default_rng():
    findings = _lint(
        """
        import numpy as np

        def sample(n):
            rng = np.random.default_rng()
            return rng.normal(size=n)
        """
    )
    assert _rules_of(findings) == ["R4"]


def test_r4_fires_on_module_scope_rng():
    findings = _lint(
        """
        import jax

        _KEY = jax.random.PRNGKey(0)
        """
    )
    assert _rules_of(findings) == ["R4"]
    assert "module scope" in findings[0].message


def test_r4_fires_on_set_iteration():
    findings = _lint(
        """
        def merge(items):
            out = []
            for x in set(items):
                out.append(x)
            return out
        """
    )
    assert _rules_of(findings) == ["R4"]


def test_r4_silent_on_seeded_rng_and_sorted_set():
    assert (
        _lint(
            """
            import numpy as np

            def sample(n, seed):
                rng = np.random.default_rng(seed)
                vals = rng.normal(size=n)
                return [v for v in sorted(set(vals.tolist()))]
            """
        )
        == []
    )


# -- R5: float64 discipline in ops/ -------------------------------------------

R5_BAD = """
    import numpy as np
    import jax.numpy as jnp

    def kernel(x):
        return jnp.zeros(x.shape, dtype=np.float64)
"""


def test_r5_fires_on_float64_in_ops():
    findings = _lint(R5_BAD, path="spark_rapids_ml_tpu/ops/fake.py")
    assert _rules_of(findings) == ["R5"]
    assert "f64" in findings[0].message or "float64" in findings[0].message


def test_r5_scoped_to_ops_dirs():
    # the same snippet outside ops/ is not R5's business
    assert _lint(R5_BAD, path="spark_rapids_ml_tpu/models/fake.py") == []


def test_r5_fires_on_dtype_string_and_builtin_float():
    findings = _lint(
        """
        import numpy as np

        def kernel(x):
            a = np.zeros(3, dtype="float64")
            b = np.zeros(3, dtype=float)
            return a, b
        """,
        path="benchmark/ops/fake.py",
    )
    assert len(findings) == 2
    assert _rules_of(findings) == ["R5"]


def test_r5_silent_on_float32():
    assert (
        _lint(
            """
            import numpy as np
            import jax.numpy as jnp

            def kernel(x):
                return jnp.zeros(x.shape, dtype=np.float32)
            """,
            path="spark_rapids_ml_tpu/ops/fake.py",
        )
        == []
    )


# -- pragmas, baseline, rule selection ---------------------------------------


def test_pragma_suppresses_on_line_and_line_above():
    src = """
        import numpy as np

        def sample(n):
            return np.random.normal(size=n)  # graftlint: disable=R4 (test fixture)
    """
    assert _lint(src) == []
    src_above = """
        import numpy as np

        def sample(n):
            # graftlint: disable=R4 (test fixture)
            return np.random.normal(size=n)
    """
    assert _lint(src_above) == []


def test_pragma_is_rule_specific():
    src = """
        import numpy as np

        def sample(n):
            return np.random.normal(size=n)  # graftlint: disable=R1 (wrong rule)
    """
    assert _rules_of(_lint(src)) == ["R4"]


def test_pragma_reason_parses():
    pragmas = collect_pragmas(
        "x = 1  # graftlint: disable=R1, R5 (host-side math)\n"
    )
    assert pragmas == {1: {"R1", "R5"}}


def test_rule_selection():
    both = """
        import numpy as np
        import jax

        def f(x, n):
            np.random.seed(0)
            for i in range(n):
                y = jax.numpy.sum(x)
                print(float(y))
    """
    assert _rules_of(_lint(both)) == ["R1", "R4"]
    assert _rules_of(_lint(both, rules=["R4"])) == ["R4"]


def test_baseline_demotes_then_catches_new(tmp_path):
    findings = _lint(R1_BAD_LOOP, path="pkg/mod.py")
    assert findings
    baseline_file = tmp_path / "baseline.json"
    ids = write_baseline(str(baseline_file), findings)
    assert len(ids) == len(findings)
    baseline = load_baseline(str(baseline_file))
    assert isinstance(baseline, set) and baseline == set(ids)
    errors, warnings = apply_baseline(findings, baseline)
    assert errors == [] and len(warnings) == len(findings)
    # a second occurrence of the same fingerprint gets a `~1` id the
    # baseline has never seen — an error again
    doubled = findings + findings
    errors, warnings = apply_baseline(doubled, baseline)
    assert len(errors) == len(findings) and len(warnings) == len(findings)


def test_baseline_v1_counts_still_apply():
    # legacy count-budget baselines (pre-v2 checkouts) keep working
    findings = _lint(R1_BAD_LOOP, path="pkg/mod.py")
    counts = {f"{f.path}::{f.rule}": len(findings) for f in findings}
    errors, warnings = apply_baseline(findings, counts)
    assert errors == [] and len(warnings) == len(findings)
    doubled = findings + findings
    errors, warnings = apply_baseline(doubled, counts)
    assert len(errors) == len(findings)


# -- R6: raw wall clocks outside srml-scope -----------------------------------

R6_BAD = """
    import time

    def _dispatch(self, batch):
        t0 = time.perf_counter()
        run(batch)
        return time.time() - t0
"""

R6_GOOD = """
    from .. import profiling

    def _dispatch(self, batch):
        t0 = profiling.now()
        with profiling.span("serve.dispatch"):
            run(batch)
        return profiling.now() - t0
"""

R6_MONOTONIC_OK = """
    import time

    def poll(deadline):
        while time.monotonic() < deadline:
            time.sleep(0.01)
"""


def test_r6_fires_on_raw_clock_in_package_module():
    findings = _lint(R6_BAD, path="spark_rapids_ml_tpu/serving/engine.py")
    assert _rules_of(findings) == ["R6"]
    assert len(findings) == 2  # perf_counter AND time.time
    assert "profiling.now()" in findings[0].message


def test_r6_scoped_to_the_package_and_exempts_profiling():
    # profiling.py is the clock's home
    assert _lint(R6_BAD, path="spark_rapids_ml_tpu/profiling.py") == []
    # benchmark/test harness code may time however it likes
    assert _lint(R6_BAD, path="benchmark/base.py") == []
    assert _lint(R6_BAD, path="tests/test_x.py") == []


def test_r6_silent_on_srml_scope_and_monotonic():
    assert _lint(R6_GOOD, path="spark_rapids_ml_tpu/serving/engine.py") == []
    # deadline polling (monotonic/sleep) is control flow, not observability
    assert (
        _lint(R6_MONOTONIC_OK, path="spark_rapids_ml_tpu/parallel/runner.py")
        == []
    )


def test_r6_pragma_escape():
    src = """
        import time

        def boot():
            t0 = time.perf_counter()  # graftlint: disable=R6 (pre-profiling bootstrap)
            return t0
    """
    assert _lint(src, path="spark_rapids_ml_tpu/x.py") == []


# -- R7: every thread must be named -------------------------------------------

R7_BAD = """
    import threading

    def start(fn):
        t = threading.Thread(target=fn, daemon=True)
        t.start()
        return t
"""

R7_BAD_FROM_IMPORT = """
    from threading import Thread, Timer

    def start(fn):
        Timer(1.0, fn).start()
        return Thread(target=fn)
"""

R7_GOOD = """
    import threading

    def start(fn, name):
        t = threading.Thread(target=fn, name=f"srml-x-{name}", daemon=True)
        t.start()
        return t
"""


def test_r7_fires_on_unnamed_thread_in_package_module():
    findings = _lint(R7_BAD, path="spark_rapids_ml_tpu/serving/engine.py")
    assert _rules_of(findings) == ["R7"]
    assert "name=" in findings[0].message


def test_r7_resolves_from_import_aliases_and_timer():
    findings = _lint(
        R7_BAD_FROM_IMPORT, path="spark_rapids_ml_tpu/watch.py"
    )
    assert _rules_of(findings) == ["R7"]
    assert len(findings) == 2  # Thread AND Timer


def test_r7_silent_on_named_threads_and_out_of_scope():
    assert _lint(R7_GOOD, path="spark_rapids_ml_tpu/serving/engine.py") == []
    # benchmark/test harness threads may stay anonymous
    assert _lint(R7_BAD, path="benchmark/audit_knn.py") == []
    assert _lint(R7_BAD, path="tests/test_x.py") == []


def test_r7_pragma_escape():
    src = """
        import threading

        def start(fn):
            return threading.Thread(target=fn)  # graftlint: disable=R7 (3p callback contract)
    """
    assert _lint(src, path="spark_rapids_ml_tpu/x.py") == []


# -- R8: remote-DMA confinement + paired start/wait ---------------------------

R8_REMOTE_OUTSIDE = """
    from jax.experimental.pallas import tpu as pltpu

    def ring_kernel(x_ref, o_ref, send_sem, recv_sem, dst):
        copy = pltpu.make_async_remote_copy(
            src_ref=x_ref, dst_ref=o_ref,
            send_sem=send_sem, recv_sem=recv_sem, device_id=(dst,),
        )
        copy.start()
        copy.wait()
"""

R8_UNPAIRED_START = """
    from jax.experimental.pallas import tpu as pltpu

    def kernel(hbm_ref, vmem_ref, sem):
        dma = pltpu.make_async_copy(hbm_ref, vmem_ref, sem)
        dma.start()
        vmem_ref[...] = vmem_ref[...] * 2.0
"""

R8_PAIRED_OK = """
    from jax.experimental.pallas import tpu as pltpu

    def kernel(hbm_ref, vmem_ref, sem):
        dma = pltpu.make_async_copy(hbm_ref, vmem_ref, sem)
        dma.start()
        dma.wait()
"""


def test_r8_fires_on_remote_copy_outside_exchange():
    findings = _lint(
        R8_REMOTE_OUTSIDE, path="spark_rapids_ml_tpu/ops/pallas_knn.py"
    )
    assert _rules_of(findings) == ["R8"]
    assert "parallel/exchange.py" in findings[0].message


def test_r8_remote_copy_allowed_in_exchange():
    # rules=["R8"]: the fixture's bare copy.wait() is R9 material at this
    # path (the real exchange.py pragmas it with the DMA-has-no-timeout
    # reason); this test is about R8 confinement only
    assert (
        _lint(
            R8_REMOTE_OUTSIDE,
            path="spark_rapids_ml_tpu/parallel/exchange.py",
            rules=["R8"],
        )
        == []
    )


def test_r8_fires_on_unpaired_start():
    findings = _lint(
        R8_UNPAIRED_START, path="spark_rapids_ml_tpu/ops/pallas_knn.py"
    )
    assert _rules_of(findings) == ["R8"]
    assert "wait()" in findings[0].message


def test_r8_silent_on_paired_start_wait_and_out_of_scope():
    assert (
        _lint(R8_PAIRED_OK, path="spark_rapids_ml_tpu/ops/pallas_knn.py")
        == []
    )
    # non-package code (docs snippets, tests) is out of scope
    assert _lint(R8_UNPAIRED_START, path="tests/test_x.py") == []


def test_r8_pragma_escape():
    src = """
        from jax.experimental.pallas import tpu as pltpu

        def kernel(hbm_ref, vmem_ref, sem):
            dma = pltpu.make_async_copy(hbm_ref, vmem_ref, sem)
            dma.start()  # graftlint: disable=R8 (waited by the out_shape semaphore)
            return dma
    """
    assert _lint(src, path="spark_rapids_ml_tpu/ops/x.py") == []


# -- R9: unbounded waits + silent teardown swallows ---------------------------

R9_BAD_WAITS = """
    def collect(fut, lock, worker):
        out = fut.result()
        lock.acquire()
        worker.join()
        return out
"""

R9_BAD_SWALLOW = """
    def teardown(ctx):
        try:
            ctx.shutdown()
        except Exception:
            pass
"""

R9_GOOD = """
    import logging

    log = logging.getLogger(__name__)

    def collect(fut, lock, worker, parts, cond, remaining):
        out = fut.result(timeout=30.0)
        lock.acquire(timeout=1.0)
        worker.join(5.0)
        cond.wait(remaining)      # a deadline variable bounds it
        joined = "".join(parts)   # str.join always takes its iterable
        return out, joined

    def teardown(ctx):
        try:
            ctx.shutdown()
        except Exception as exc:
            log.warning("shutdown failed: %s", exc)  # logged, not swallowed
        try:
            ctx.unlink()
        except OSError:
            pass  # narrow handler: deliberate, in scope of the except type
"""


def test_r9_fires_on_unbounded_waits_in_parallel_and_serving():
    for path in (
        "spark_rapids_ml_tpu/parallel/runner.py",
        "spark_rapids_ml_tpu/serving/engine.py",
    ):
        findings = _lint(R9_BAD_WAITS, path=path)
        assert _rules_of(findings) == ["R9"]
        assert len(findings) == 3  # result, acquire, join
        assert "timeout" in findings[0].message


def test_r9_fires_on_silent_broad_swallow():
    findings = _lint(R9_BAD_SWALLOW, path="spark_rapids_ml_tpu/parallel/context.py")
    assert _rules_of(findings) == ["R9"]
    assert "logged event" in findings[0].message


def test_r9_silent_on_bounded_waits_logged_handlers_and_narrow_types():
    assert _lint(R9_GOOD, path="spark_rapids_ml_tpu/serving/batcher.py") == []


def test_r9_scoped_to_parallel_and_serving():
    # solver/engine modules block only on the device runtime — out of scope
    assert _lint(R9_BAD_WAITS, path="spark_rapids_ml_tpu/ops/knn.py") == []
    assert _lint(R9_BAD_SWALLOW, path="spark_rapids_ml_tpu/watch.py") == []
    assert _lint(R9_BAD_WAITS, path="benchmark/audit_knn.py") == []


def test_r9_pragma_escape():
    src = """
        def hop(copy):
            copy.wait()  # graftlint: disable=R9 (DMA completion has no timeout)
    """
    assert _lint(src, path="spark_rapids_ml_tpu/parallel/exchange.py") == []


# -- R10: raw-socket confinement + bounded socket waits -----------------------

R10_SOCKET_OUTSIDE = """
    import socket

    def pick_port():
        with socket.socket() as s:
            s.bind(("", 0))
            return s.getsockname()[1]

    def dial(addr):
        return socket.create_connection(addr, timeout=5.0)
"""

R10_UNBOUNDED_RECV = """
    def read_all(sock, conn_listener):
        conn, _ = conn_listener.accept()
        return sock.recv(4096)
"""

R10_BOUNDED_RECV = """
    def read_all(sock, conn_listener):
        conn_listener.settimeout(0.25)
        sock.settimeout(0.25)
        conn, _ = conn_listener.accept()
        return sock.recv(4096)
"""


def test_r10_fires_on_raw_sockets_outside_netplane():
    findings = _lint(
        R10_SOCKET_OUTSIDE, path="spark_rapids_ml_tpu/parallel/context.py"
    )
    assert _rules_of(findings) == ["R10"]
    assert len(findings) == 2  # socket.socket + socket.create_connection
    assert "parallel/netplane.py" in findings[0].message


def test_r10_constructors_allowed_inside_netplane():
    assert _lint(
        R10_SOCKET_OUTSIDE,
        path="spark_rapids_ml_tpu/parallel/netplane.py",
    ) == []


def test_r10_fires_on_unbounded_recv_accept_in_netplane():
    findings = _lint(
        R10_UNBOUNDED_RECV, path="spark_rapids_ml_tpu/parallel/netplane.py"
    )
    assert _rules_of(findings) == ["R10"]
    assert len(findings) == 2  # accept + recv, both timeout-less
    assert "settimeout" in findings[0].message


def test_r10_silent_when_settimeout_precedes_the_wait():
    assert _lint(
        R10_BOUNDED_RECV, path="spark_rapids_ml_tpu/parallel/netplane.py"
    ) == []


def test_r10_scoped_to_the_package():
    # tests/benchmarks may socket however they like; the recv discipline
    # applies only inside the confined module itself
    assert _lint(R10_SOCKET_OUTSIDE, path="tests/chaos_driver.py") == []
    assert _lint(
        R10_UNBOUNDED_RECV, path="spark_rapids_ml_tpu/serving/engine.py"
    ) == []


def test_r10_pragma_escape():
    src = """
        import socket

        def legacy_probe():
            s = socket.socket()  # graftlint: disable=R10 (pre-wire probe, bounded by caller)
            return s
    """
    assert _lint(src, path="spark_rapids_ml_tpu/utils.py") == []


# -- the gate: the real tree is clean -----------------------------------------


@pytest.mark.parametrize("pkg", ["spark_rapids_ml_tpu", "benchmark", "tests"])
def test_tree_is_graftlint_clean(pkg):
    findings = lint_paths([os.path.join(REPO, pkg)])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_cli_reports_per_rule_counts(capsys):
    from tools.graftlint.__main__ import main

    rc = main([os.path.join(REPO, "spark_rapids_ml_tpu", "utils.py")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "R1[host-sync]=" in out and "clean" in out
