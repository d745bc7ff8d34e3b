"""chipbench: the repo's benchmark.  Everything the yardstick needs lives here
(traffic drivers, trace reduction, peaks, operation counts, plain references, the
comparison that decides `correct` and its limits); from the program it takes only
the system under test and its spans, counters and kernel names.  See PERF.md."""
