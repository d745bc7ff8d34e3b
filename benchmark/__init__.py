#
# What is left of the port of the reference's python/benchmark/: gen_data.py,
# its data generators, and audit_knn.py, the float64 ground-truth audit of
# the kNN kernels on the chip.  The harness that timed estimators from here
# was removed by PR 28: the repo's benchmark is chipbench/ (BENCHMARK.json).
#
