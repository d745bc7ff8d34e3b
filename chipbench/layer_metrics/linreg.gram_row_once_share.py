"""Of the rows the dense LinearRegression fits' Gram scans sent through their
products, the share that are rows of the table: the program's counters
linreg.gram_rows over linreg.gram_rows_multiplied, both static at dispatch
(the staged table's rows, and the rows of the blocks the scan walks, by the
plan the scan itself uses).  100 when every row goes through the products
once; 93.9 (400,000 / 425,984) for a scan whose clamped last chunk walks
25,984 rows a second time under a weight of zero.  A program without the
counters (the parent of the PR that added them) reads nothing."""
from chipbench import program


def read(ctx):
    counters = program.counters()
    multiplied = counters.get("linreg.gram_rows_multiplied", 0)
    return 100.0 * counters.get("linreg.gram_rows", 0) / multiplied if multiplied else None
