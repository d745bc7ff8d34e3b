"""A cross-validation sweep of lane-batched L-BFGS on a dense (n, d) float32 table:
bytes read from HBM."""


def solve_bytes(rows: int, cols: int, scans: float, itemsize: int = 4) -> float:
    """ONE read of X a scan (an evaluation the lanes share).  The least any
    implementation needs: all folds and candidates take their forward product and
    their gradient from the same rows, so a scan that reads each row once serves
    every lane.  Autodiff over the lane einsum reads X twice a scan, so it cannot
    pass half of this bound; a one-pass lane kernel could approach it."""
    return float(rows) * cols * itemsize * scans
