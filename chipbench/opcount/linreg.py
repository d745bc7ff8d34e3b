"""A linear-regression fit by sufficient statistics: floating-point operations of
the Gram pass, and the bytes a coordinate-descent step reads."""


def gram_flops(rows: int, cols: int) -> float:
    """2 * rows * cols^2: the (cols, rows) x (rows, cols) product X'X, counted
    ONCE.  Left out: X'y, the means, the weights' multiply.  The program computes
    it at Precision.HIGHEST, six bfloat16 passes of the MXU for one float32
    product, so the share of the bf16 peak this gives has its ceiling at 16.7%
    (33% were it three passes); and it computes both triangles of a symmetric
    matrix.  A kernel that computes one triangle does half these operations in
    truth: the count would then have to be halved, which is a `benchmark` PR's
    to do, not the PR's that brings the kernel."""
    return 2.0 * rows * cols * cols


def cd_row_bytes(cols: int, coordinates: int, itemsize: int = 4) -> float:
    """One row of the (cols, cols) Gram a coordinate: what a covariance-update
    step must read (the coefficients, 12 KB, stay on the chip).  A lower bound."""
    return float(cols) * itemsize * coordinates
