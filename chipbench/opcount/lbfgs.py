"""L-BFGS on a dense (n, d) float32 design matrix: bytes read from HBM."""


def bytes_read(rows: int, cols: int, iters: int, itemsize: int = 4) -> float:
    """One read of X per iteration.  An iteration needs at least the forward
    product X @ w; the gradient's X.T @ r and every further line-search
    evaluation read X again, so this is a lower bound."""
    return float(rows) * cols * itemsize * iters
