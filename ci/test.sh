#!/bin/bash
# CI entry point: each gate once.
#   1. byte-compile + graftlint     2. native build
#   3. the Tier-1 tests, as the driver runs them (SRML_CI_FULL=1: the slow pass too)
#   4. the benchmark's own CPU tests (chipbench/tests)
# Speed is not a CI matter: it is measured on the chip, by BENCHMARK.json's cells
# (docs/benchmarking.md).  The focused re-runs and the CPU smokes of the
# pre-round harness that stood here were removed by PR 28; what each asserted
# is a test under tests/ (CHANGES.md, PR 28, names them).
set -ex

cd "$(dirname "$0")/.."

# 1. static checks: byte-compile everything, then graftlint, the JAX/TPU
#    invariant checker (R1-R12, docs/graftlint.md).  ONE whole-package run: R11
#    and R12 need every module parsed together for the package-wide lock graph,
#    and --fail-on-new against the committed baseline makes any NEW finding a
#    build error while audited debt stays visible as warnings.
python -m compileall -q spark_rapids_ml_tpu benchmark chipbench tools tests \
    chip_smoke.py __graft_entry__.py
python -m tools.graftlint spark_rapids_ml_tpu benchmark \
    --baseline ci/graftlint-baseline.json --fail-on-new

# 2. native runtime build (optional at run time: without it the numpy routes
#    are the path)
make -C native

# 3. Tier-1: the fast suite on the virtual 8-device CPU mesh (tests/conftest.py
#    pins the platform and the device count), with the driver's own options
#    (/root/TESTS_LAST_RUN.json: six workers, a file to a worker, no cache
#    provider).  Heavy multi-process and deep-forest paths are @slow-tagged.
#    --durations keeps the top time sinks visible so the budget cannot creep
#    unnoticed; both wall-clocks are printed.
t0=$SECONDS
env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
    --dist loadfile -p no:randomly --durations=10
echo "CI budget: Tier-1 took $((SECONDS - t0))s"
if [ "${SRML_CI_FULL:-0}" = "1" ]; then
    # the nightly budget: everything @slow (3- and 4-process multicontroller
    # fits and kneighbors on both control planes, bitwise across planes; the
    # hardware kNN audit, which skips off the chip), in one process: these
    # tests start jax children of their own
    t1=$SECONDS
    python -m pytest tests/ -x -q --runslow -m slow
    # the runtime half of graftlint-cc: the chaos matrix and the serving and
    # elastic recovery gates with the lock-order validator armed and nothing
    # else (debug_nans and the transfer guard stay off, so timings hold).  A
    # violation raises out of the acquiring thread, so a green run IS the
    # zero-violations assertion; no other gate sees the alias and cross-module
    # edges the static pass cannot follow (docs/graftlint.md#r11).
    SRML_SANITIZE=lockdep python -m pytest -q \
        tests/test_faults.py tests/test_netplane.py
    SRML_SANITIZE=lockdep python -m pytest -q \
        tests/test_serving.py tests/test_autoscale.py \
        -k "shield or worker_death or wedge_then or drain_during or budget \
            or rolls_up or concurrent_allocate_release or preemption_storm"
    echo "CI budget: slow pass took $((SECONDS - t1))s"
fi

# 4. the benchmark the driver runs on the chip, checked here on the CPU: its
#    drivers at a test's size, the trace reduction on recorded traces, the
#    controls and planted faults that must turn `correct` false
python -m pytest chipbench/tests -q -p no:cacheprovider

echo "CI OK"
