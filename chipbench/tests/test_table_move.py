"""lloyd.table_move_ms_per_job on a hand-made trace summary: which operation
names it counts (PR 24's `kmeans_fit` breakdown, as trace_reduce.short_name
writes them), and that a fusion of the table's shape is not among them."""
from types import SimpleNamespace

import pytest

from chipbench import harness, trace_reduce as tr

reader = harness.load_reader("lloyd.table_move_ms_per_job")

OPS = [
    ["fusion.74 fusion (bf16[32768]", 3.817],
    ["fusion.75 fusion f32[1000,3000]", 3.678],
    ["pad.23 pad bf16[425984,3000]", 2.096],             # counted: the padded copy, 30 times a fit
    ["pad.6.clone pad f32[425984,3000]", 0.140],         # counted
    ["copy copy f32[400000,3000]", 0.136],               # counted
    ["copy.2 copy f32[400000,3000]", 0.136],             # counted
    ["convert.11 convert bf16[400000,3000]", 0.102],     # counted
    ["fusion.9 fusion bf16[400000,3000]", 0.5],          # a fusion works on the rows: not counted
    ["copy.6 copy f32[6784,3000]", 0.01],                # fewer rows than a chip holds: not counted
    ["copy.37 copy f32[1000,3000]", 0.02],               # the centres: not counted
    ["pad.24 pad f32[425984]", 0.001],                   # the weights: not a table
    ["convert.3 convert bf16[400000,128]", 0.3],         # another width: not counted
]


def _ctx(ops=OPS, jobs=9):
    return SimpleNamespace(
        trace={"device_ops": ops}, jobs=[{}] * jobs,
        config={"data": {"rows_per_chip": 400000, "cols": 3000}},
    )


def test_counts_the_pads_copies_and_converts_of_the_table():
    assert reader.read(_ctx()) == pytest.approx(1e3 * (2.096 + 0.140 + 0.136 + 0.136 + 0.102) / 9)   # 290 ms a job
    counted = [name.split(" ")[0] for name, _ in OPS if reader.moves_table(name, 400000, 3000)]
    assert counted == ["pad.23", "pad.6.clone", "copy", "copy.2", "convert.11"]


def test_a_trace_without_such_operations_reads_zero_and_no_trace_reads_nothing():
    assert reader.read(_ctx(ops=OPS[:2])) == 0.0
    assert reader.read(SimpleNamespace(trace=None, jobs=[{}], config={})) is None
    assert reader.read(SimpleNamespace(trace={"device_ops": OPS}, config={})) is None


def test_names_are_read_as_the_reduction_writes_them():
    hlo = "%pad.23 = bf16[425984,3000]{1,0:T(8,128)(2,1)} pad(%get-tuple-element.447, %constant.49), padding=0_25984x0_0"
    assert tr.short_name(hlo) == "pad.23 pad bf16[425984,3000]"
    assert reader.moves_table(tr.short_name(hlo), 400000, 3000)
    fused = "%fusion.9 = bf16[400000,3000]{1,0:T(8,128)(2,1)} fusion(%copy.2), kind=kLoop, calls=%fused_computation.9"
    assert not reader.moves_table(tr.short_name(fused), 400000, 3000)
