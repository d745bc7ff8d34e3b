"""Lloyd iterations: floating-point operations of the distance products."""


def flops(rows: int, cols: int, k: int, iters: int) -> float:
    """2*n*d*k per iteration: the (n, d) x (d, k) distance product alone.  The
    centre update (a second product of the same size as the program writes it),
    the argmin and the norms are left out, so this is a lower bound."""
    return 2.0 * rows * cols * k * iters
