"""A forest fit's histogram work: floating-point operations of the one-hot
products, and the bytes of one binning pass."""


def shallow_levels(classes: int, slots: int = 128) -> int:
    """The deepest level whose (node, class) slots fit one scan: 2^l * classes <= slots."""
    level = 0
    while 2 ** (level + 1) * classes <= slots:
        level += 1
    return level


def hist_flops(rows: int, trees: int, features: int, bins: int, classes: int, max_depth: int) -> float:
    """2 * rows * features * bins * slots for every tree and every level that
    searches a split (levels 0 .. max_depth - 1): a histogram is the product of a
    (slots, rows) operand (a row's weight at its (node, class) slot) with the
    (rows, bins) one-hot of a feature's bins.  Slots are a level's nodes times the
    classes up to the deepest level that fits 128 slots, and from there on the
    nodes of a row's own subtree (rooted one level below it) times the classes.

    Counted: the real rows, the features of the subset (54, not the 64 rows the
    kernel's feature blocks hold), the slots a tree needs.  Left out: the row
    tiles' and segments' padding rows, slots padded up to 8 or shared out to 128,
    trees recomputed in a clamped last window, the leaf level's totals, the split
    search and the routing.  So the share it gives is a lower bound on how busy
    the kernels keep the MXU, and as far below it as the one-hot products are
    from dense ones: most of a one-hot operand is zero by construction."""
    top = shallow_levels(classes)
    slots = sum(2 ** (level if level <= top else level - top - 1) * classes for level in range(max_depth))
    return 2.0 * rows * trees * features * bins * slots


def bin_bytes(rows: int, cols: int, itemsize: int = 4) -> float:
    """One read of the table and one write of a byte a cell."""
    return float(rows) * cols * (itemsize + 1)
