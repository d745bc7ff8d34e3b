"""Of the tall fits of the process, the share whose update passes went through the
one-read Pallas kernel `lloyd_tall_pass` (ops/lloyd_tall_pass.py: a tile of the
feature-major table read once, cut into its bfloat16 pieces once, distances, argmin
and sums from them in VMEM): the program's counters lloyd.tall_kernel_fits over
lloyd.tall_fits, both static at dispatch.  A program without the counter, whose pass
is XLA's two product fusions a block, reads 0; nothing where no tall fit ran."""
from chipbench import program


def read(ctx):
    counters = program.counters()
    fits = counters.get("lloyd.tall_fits", 0)
    return 100.0 * counters.get("lloyd.tall_kernel_fits", 0) / fits if fits else None
