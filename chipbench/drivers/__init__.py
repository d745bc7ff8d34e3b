"""Traffic drivers: one per kind of traffic, named by the mix's file.  A driver
has setup(ctx), window(ctx, seconds), check(ctx) -> comparisons, tally(ctx) and
metrics(ctx) -> its cells' end-to-end metrics.  What it drives and how the outputs
are compared is the subject's: subjects/<family>.<driver>.py."""
