"""Rule 1 of set-up: the seed never enters a compile key.  Every jitted function
of the harness lowers to the same text for two seeds."""
import pytest

from chipbench import harness
from chipbench.clock import PhaseClock

from .conftest import small


def _lowered(cell_name, seed):
    bench = harness.load_benchmark()
    cell = dict(harness.find_cell(bench, cell_name), chips=1)
    clock = PhaseClock()
    clock.mark("process_start")
    ctx, driver = harness.prepare(bench, cell, seed, 0.2, clock, small(cell_name))
    driver.setup(ctx)
    assert ctx.jitted, "the adapter registers every function it jits"
    return {name: fn.lower(*args).as_text() for name, (fn, args) in ctx.jitted.items()}


@pytest.mark.parametrize("cell", ["kmeans_fit", "logreg_fit"])
def test_two_seeds_lower_alike(cell):
    a, b = _lowered(cell, 7), _lowered(cell, 2**31 + 123456)
    assert a.keys() == b.keys()
    for name in a:
        assert a[name] == b[name], name
