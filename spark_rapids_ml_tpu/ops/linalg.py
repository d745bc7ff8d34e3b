#
# Distributed linear-algebra building blocks (pure jax, mesh-aware).
#
# TPU-native replacement for cuML's PCAMG / tall-skinny covariance kernels
# (used by the reference at feature.py:217-238) and for the raft eigDC +
# sign-flip pipeline of the legacy JNI path (rapidsml_jni.cu:215-269).  All
# functions take row-sharded global arrays; jnp matmuls over the sharded row
# axis compile to per-shard partial products + psum over ICI/DCN (GSPMD), so
# no explicit collectives appear here.
#

from __future__ import annotations

from functools import partial
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..parallel.mesh import DATA_AXIS

# Solver matmuls run at HIGHEST precision: on TPU the default f32 matmul is a
# single-pass bf16 MXU product (~2^-9 relative error per element), which is
# fine for iterative *search* (the KMeans assignment loop keeps it) but not
# for quantities we return or solve against — hardware runs showed OLS
# coefficients off 3.5% vs sklearn and kNN distances failing parity until
# gram/covariance/projection/distance matmuls were pinned.  cuML computes all
# of these in exact f32 FMA; HIGHEST (bf16_6x) restores that at negligible
# cost for one-pass contractions.
SOLVER_PRECISION = jax.lax.Precision.HIGHEST


def exact_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """a @ b with full-f32 MXU products (see SOLVER_PRECISION); bf16 inputs
    accumulate and return f32 so cancellation-prone sums stay exact."""
    out_dtype = jnp.promote_types(a.dtype, b.dtype)
    pet = jnp.float32 if out_dtype == jnp.dtype(jnp.bfloat16) else None
    return jnp.matmul(a, b, precision=SOLVER_PRECISION, preferred_element_type=pet)


def exact_gather_matmul(X: jax.Array, stacked: jax.Array, lanes: jax.Array) -> jax.Array:
    """The lane-gathered form of exact_matmul for multiplexed predict
    kernels (srml-lanes): out[r] = X[r] @ stacked[lanes[r]].T, i.e. each
    row contracts against ITS lane's (K, D) parameter slab.  (N, D) x
    (L, K, D) gathered by (N,) int32 -> (N, K), with the same precision
    discipline as exact_matmul so a lane-batched score is the exact same
    contraction the dedicated per-model kernel runs."""
    g = jnp.take(stacked, lanes, axis=0)  # (N, K, D)
    out_dtype = jnp.promote_types(X.dtype, stacked.dtype)
    pet = jnp.float32 if out_dtype == jnp.dtype(jnp.bfloat16) else None
    return jnp.einsum(
        "nd,nkd->nk", X, g, precision=SOLVER_PRECISION, preferred_element_type=pet
    )


def sign_flip(components: jax.Array) -> jax.Array:
    """Deterministic eigenvector signs: flip each row so its largest-|.|
    element is positive (semantics of the reference's thrust signFlip kernel,
    rapidsml_jni.cu:35-61, and cuML MG PCA)."""
    idx = jnp.argmax(jnp.abs(components), axis=1)
    picked = jnp.take_along_axis(components, idx[:, None], axis=1)
    return components * jnp.sign(picked)


def weighted_moments(X: jax.Array, w: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (wsum, mean, scatter) where scatter = sum_i w_i x_i x_i^T.

    X: (N, D) row-sharded, w: (N,) row-sharded (0 for padded rows).  The
    contraction over the sharded axis becomes a psum inserted by XLA.

    NOTE: this is the monolithic GSPMD form.  For large N on TPU prefer the
    mesh+chunk path of the pca kernels below: XLA's compile time on a single
    (D, N) @ (N, D) contraction was seen to grow pathologically with N on a
    TPU backend, while a chunk-scanned accumulation of the same FLOPs
    compiles quickly and runs at the same throughput."""
    wsum = w.sum()
    mean = (X * w[:, None]).sum(axis=0) / wsum
    scatter = exact_matmul((X * w[:, None]).T, X)
    return wsum, mean, scatter


# Width of a column panel of the chunk scan's X'WX (_local_moments).  Narrower
# panels leave less of the lower triangle in their diagonal blocks but read
# and split the right-hand operand once a panel; on a v5e at 400,000 x 3000
# the pass takes 182.6 ms at 1024, 159.3 at 512 and 153.5 at 256, where the
# whole product takes 270.2 (PERF.md, PR 31).
GRAM_PANEL_WIDTH = 256


def gram_panels(d: int) -> int:
    """How many column panels the chunk scan cuts a (d, d) scatter into: one
    up to GRAM_PANEL_WIDTH columns (the whole product), above it
    ceil(d / GRAM_PANEL_WIDTH), each against the columns from its own start
    on, so that only the upper triangle goes through the MXU."""
    return max(1, -(-d // GRAM_PANEL_WIDTH))


def _mirror_block_rows(rows, d: int) -> jax.Array:
    """The (d, d) scatter from its block rows (panel p's is (width, d - s_p),
    the columns from its own start s_p on): the upper triangle as computed,
    the lower one its mirror, so the result equals its transpose to the bit.
    One block row is the whole product and is returned as it is."""
    if len(rows) == 1:
        return rows[0]
    upper = jnp.concatenate(
        [jnp.pad(r, ((0, 0), (d - r.shape[1], 0))) for r in rows], axis=0
    )
    i = jnp.arange(d)
    return jnp.where(i[:, None] <= i[None, :], upper, upper.T)


def scan_rows(n_loc: int, chunk: int) -> Tuple[int, int]:
    """How the chunk scan (_local_moments) walks a shard of n_loc rows: (whole
    chunks, rows left over).  The whole chunks go through a loop over the
    chunk index, the rows left over are one block of their own shape, so the
    products are handed whole * chunk + left over = n_loc rows, each once.
    The scan plans by this and the fit's counters count by it
    (models/linear_regression._count_fit)."""
    return divmod(n_loc, chunk)


def _local_moments(
    X_loc: jax.Array, w_loc: jax.Array, chunk: int, y_loc: jax.Array = None
):
    """Per-shard weighted moments from a walk over row chunks: compile time is
    independent of the shard's row count and no padded copy of the shard is
    materialized.  Rows are read where they lie (as ops/kmeans.py's
    _chunked_assign_stats reads them): scan_rows' whole chunks are sliced out
    of the shard by a loop over the chunk index, and the rows left over are
    one more block of their own (smaller, static) shape, so no row goes
    through the products twice.  Either half drops out when it is empty (a
    shard no longer than a chunk is that one block and no loop).

    The scatter X'WX is symmetric, so a block's product is taken as
    gram_panels(d) column panels, each against the columns from its own
    start on; the walk carries those block rows and the lower triangle is
    mirrored in once after it (_mirror_block_rows).

    Returns (wsum, xwsum, scatter) — plus (ywsum, Xty, y2) when `y_loc` is
    given (the linear-regression sufficient statistics)."""
    n_loc, d = X_loc.shape
    with_y = y_loc is not None
    starts = [p * GRAM_PANEL_WIDTH for p in range(gram_panels(d))]
    init = [
        jnp.zeros((), X_loc.dtype),
        jnp.zeros((d,), X_loc.dtype),
        tuple(
            jnp.zeros((min(GRAM_PANEL_WIDTH, d - s), d - s), X_loc.dtype)
            for s in starts
        ),
    ]
    if with_y:
        init += [
            jnp.zeros((), X_loc.dtype),
            jnp.zeros((d,), X_loc.dtype),
            jnp.zeros((), X_loc.dtype),
        ]
    if n_loc == 0:
        # empty shard (possible under uneven mesh layouts / direct callers):
        # zero moments, nothing to walk
        init[2] = jnp.zeros((d, d), X_loc.dtype)
        return tuple(init)
    n_full, tail = scan_rows(n_loc, chunk)
    tables = (X_loc, w_loc) + ((y_loc,) if with_y else ())

    def block(carry, xb, wb, yb=None):
        xw = xb * wb[:, None]
        out = [
            carry[0] + wb.sum(),
            carry[1] + xw.sum(axis=0),
            # each panel weights its own slice: a slice of the shared `xw`
            # would give it a user a panel, and XLA then writes the weighted
            # chunk to HBM instead of fusing the weighting into each product
            tuple(
                acc
                + exact_matmul(
                    (xb[:, s : s + GRAM_PANEL_WIDTH] * wb[:, None]).T, xb[:, s:]
                )
                for s, acc in zip(starts, carry[2])
            ),
        ]
        if with_y:
            out += [
                carry[3] + (yb * wb).sum(),
                carry[4] + exact_matmul(xw.T, yb),
                carry[5] + (yb * yb * wb).sum(),
            ]
        return tuple(out)

    def rows(start, size):
        return (jax.lax.dynamic_slice_in_dim(a, start, size) for a in tables)

    out = tuple(init)
    if n_full:
        out, _ = jax.lax.scan(
            lambda carry, i: (block(carry, *rows(i * chunk, chunk)), None),
            out,
            jnp.arange(n_full, dtype=jnp.int32),
        )
    if tail:
        out = block(out, *rows(n_full * chunk, tail))
    return out[:2] + (_mirror_block_rows(out[2], d),) + out[3:]


def _sharded_moments(X: jax.Array, w: jax.Array, mesh, chunk: int):
    """(wsum, mean, scatter) via per-shard chunked scans + one psum."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def per_device(X_loc, w_loc):
        return tuple(
            jax.lax.psum(v, DATA_AXIS)
            for v in _local_moments(X_loc, w_loc, chunk)
        )

    wsum, xwsum, G = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )(X, w)
    return wsum, xwsum / wsum, G


def _moments(X, w, mesh, chunk):
    if mesh is None:
        return weighted_moments(X, w)
    wsum, mean, G = _sharded_moments(X, w, mesh, chunk)
    return wsum, mean, G


@partial(jax.jit, static_argnames=("k", "mesh", "chunk"))
def pca_fit_kernel(
    X: jax.Array, w: jax.Array, k: int, mesh=None, chunk: int = 32768
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Distributed PCA via covariance + eigh.

    Math (not a port): cov = (sum w x x^T - n·mean mean^T) / (n - 1) with the
    row-sharded scatter psum'd by GSPMD; eigh runs replicated on the (D, D)
    covariance; top-k eigenpairs in descending order; singular values follow
    sigma_j = sqrt(lambda_j (n-1)).  Matches the observable behavior of cuML
    PCAMG as used by the reference (feature.py:217-238) incl. deterministic
    component signs.

    Returns (mean, components[k,D], explained_variance[k], explained_variance_ratio[k],
    singular_values[k]).
    """
    wsum, mean, scatter = _moments(X, w, mesh, chunk)
    return _pca_from_moments(wsum, mean, scatter, k)


def _pca_from_moments(wsum, mean, scatter, k: int):
    """Covariance + dense eigh + sign-canonicalized top-k from replicated
    weighted moments — the ONE post-moments derivation, traced identically
    by the batch kernel above and by the streaming finalize kernel below,
    so a streamed fit whose accumulated moments carry the same bits as the
    batch pass yields bit-identical components (the srml-stream equality
    contract, docs/streaming.md)."""
    cov = (scatter - wsum * jnp.outer(mean, mean)) / (wsum - 1.0)
    cov = (cov + cov.T) * 0.5
    evals, evecs = jnp.linalg.eigh(cov)  # ascending
    evals = evals[::-1]
    evecs = evecs[:, ::-1]
    top_vals = evals[:k]
    components = sign_flip(evecs[:, :k].T)
    total_var = jnp.maximum(evals.sum(), jnp.finfo(evals.dtype).tiny)
    ratio = top_vals / total_var
    singular_values = jnp.sqrt(jnp.maximum(top_vals, 0.0) * (wsum - 1.0))
    return mean, components, top_vals, ratio, singular_values


@partial(jax.jit, static_argnames=("k",))
def pca_from_moments_kernel(
    wsum: jax.Array, xwsum: jax.Array, scatter: jax.Array, k: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """PCA finalize for accumulated streaming moments: mean derived from
    the raw weighted sum exactly like the batch moment passes (xwsum/wsum
    on replicated values), then the shared _pca_from_moments tail.  Same
    return tuple as pca_fit_kernel."""
    return _pca_from_moments(wsum, xwsum / wsum, scatter, k)


def pca_finalize_moments(
    wsum, xwsum, scatter, k: int, host_eigh: bool = None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host entry for the streaming PCA finalize: the same device-vs-native
    eigh routing rule as pca_fit, applied to accumulated (wsum, xwsum,
    scatter) moments instead of a staged dataset.  Inputs are host arrays
    in the fit's compute dtype; returns numpy arrays in pca_fit's layout."""
    wsum = np.asarray(wsum)
    xwsum = np.asarray(xwsum)
    scatter = np.asarray(scatter)
    d = scatter.shape[0]
    if host_eigh is None:
        host_eigh = d >= HOST_EIGH_MIN_D and jax.default_backend() == "cpu"
    if not host_eigh:
        return tuple(
            jax.device_get(
                pca_from_moments_kernel(
                    jnp.asarray(wsum), jnp.asarray(xwsum), jnp.asarray(scatter), k
                )
            )
        )  # type: ignore[return-value]
    from .. import native

    # mirror pca_fit's host branch: covariance formed in the compute dtype,
    # then the f64 native eigh on the HOST copy
    mean = xwsum / wsum
    cov = (scatter - wsum * np.outer(mean, mean)) / (wsum - 1.0)
    cov = (cov + cov.T) * 0.5
    wsum_f = float(wsum)
    mean64 = mean.astype(np.float64)  # graftlint: disable=R5 (host-side eigh input)
    cov64 = cov.astype(np.float64)  # graftlint: disable=R5 (host-side eigh input)
    evals, comps = native.eigh_descending(cov64)
    top = np.maximum(evals[:k], 0.0)
    total = max(evals.sum(), np.finfo(np.float64).tiny)  # graftlint: disable=R5 (host-side f64 epsilon)
    return (
        mean64,
        comps[:k],
        evals[:k],
        evals[:k] / total,
        np.sqrt(top * (wsum_f - 1.0)),
    )


@partial(jax.jit, static_argnames=("mesh", "chunk"))
def covariance_kernel(
    X: jax.Array, w: jax.Array, mesh=None, chunk: int = 32768
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Mesh-distributed (wsum, mean, cov): the MXU/ICI half of PCA."""
    wsum, mean, scatter = _moments(X, w, mesh, chunk)
    cov = (scatter - wsum * jnp.outer(mean, mean)) / (wsum - 1.0)
    return wsum, mean, (cov + cov.T) * 0.5


# Max acceptable relative eigenpair residual from the subspace path; a
# converged f32 eigenpair sits around 1e-6-1e-5, an unconverged one (slow
# spectral decay) orders of magnitude higher.  Above this, pca_fit reruns
# through the exact dense eigh.
SUBSPACE_RESIDUAL_TOL = 1e-3


@partial(jax.jit, static_argnames=("k", "oversample", "n_iter", "mesh", "chunk"))
def pca_fit_subspace_kernel(
    X: jax.Array,
    w: jax.Array,
    k: int,
    oversample: int = 10,
    n_iter: int = 24,
    mesh=None,
    chunk: int = 32768,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Distributed PCA via covariance + blocked subspace iteration — the
    small-k fast path.

    Why not eigh: XLA's TPU eigh (QDWH) at D=3000 costs minutes of COMPILE
    time for a kernel that runs in under a second; subspace iteration on the
    (D, D) covariance compiles in seconds (matmuls + small solves only) and
    converges to the same top-k eigenpairs.  Total variance needs no
    spectrum: it is trace(cov).  Orthonormalization is CholeskyQR2 (two
    Gram+Cholesky passes — MXU-only, no Householder unrolling); the final
    small (k+p, k+p) Rayleigh-Ritz eigh compiles fast.

    Returns the pca_fit_kernel tuple plus a trailing convergence residual:
    max_j ||cov v_j - lambda_j v_j|| / max(lambda_1, tiny).  Subspace
    iteration converges at rate (lambda_{k+p}/lambda_k)^n_iter, so on
    slowly-decaying or near-isotropic spectra the fixed iteration count can
    leave eigenpairs inaccurate; callers (pca_fit) check the residual and
    fall back to the exact eigh path when it exceeds tolerance.
    """
    d = X.shape[1]
    p = min(d - k, oversample)
    wsum, mean, scatter = _moments(X, w, mesh, chunk)
    cov = (scatter - wsum * jnp.outer(mean, mean)) / (wsum - 1.0)
    cov = (cov + cov.T) * 0.5
    total_var = jnp.trace(cov)  # = sum of ALL eigenvalues; no spectrum needed

    def chol_qr2(Y):
        eps = jnp.finfo(Y.dtype).eps
        for _ in range(2):
            G = exact_matmul(Y.T, Y)
            G = G + (eps * jnp.trace(G)) * jnp.eye(G.shape[0], dtype=Y.dtype)
            R = jnp.linalg.cholesky(G)
            Y = jax.lax.linalg.triangular_solve(
                R, Y, left_side=False, lower=True, transpose_a=True
            )
        return Y

    key = jax.random.PRNGKey(0)
    Q0 = jax.random.normal(key, (d, k + p), dtype=X.dtype)

    def rr_residual(Q):
        """Rayleigh-Ritz on the current subspace + eigenpair residual
        relative to the spectral-norm estimate lambda_1, reusing CQ:
        cov @ V == (cov @ Q) @ evecs_top, so no second (D, D) contraction
        is paid."""
        CQ = exact_matmul(cov, Q)
        B = exact_matmul(Q.T, CQ)
        B = (B + B.T) * 0.5
        evals_s, evecs_s = jnp.linalg.eigh(B)  # ascending, (k+p, k+p): tiny
        evals = evals_s[::-1][:k]
        evecs_top = evecs_s[:, ::-1][:, :k]
        V = exact_matmul(Q, evecs_top)
        R = exact_matmul(CQ, evecs_top) - V * evals[None, :]
        scale = jnp.maximum(jnp.abs(evals[0]), jnp.finfo(evals.dtype).tiny)
        residual = jnp.sqrt((R * R).sum(axis=0)).max() / scale
        return evals, V, residual

    def iter_block(Q, steps):
        def body(_, Q):
            return chol_qr2(exact_matmul(cov, Q))

        return jax.lax.fori_loop(0, steps, body, Q)

    # ADAPTIVE iteration (advisor finding, round 1): convergence rate is
    # (lambda_{k+p}/lambda_k)^n_iter, so near-equal leading eigenvalues
    # (e.g. an isotropic low-rank factor block) defeat any fixed count.
    # Keep iterating in n_iter-sized blocks — each block costs ~n_iter
    # (D, D) @ (D, k+p) matmuls, orders of magnitude cheaper than the
    # dense-eigh fallback — until the residual passes or the round budget
    # is spent; callers fall back to exact eigh only in the latter case.
    Q1 = iter_block(chol_qr2(Q0), n_iter)
    evals0, V0, res0 = rr_residual(Q1)

    def cond(carry):
        _, _, _, residual, rounds = carry
        return (residual > SUBSPACE_RESIDUAL_TOL) & (rounds < 4)

    def more(carry):
        Q, _, _, _, rounds = carry
        Q = iter_block(Q, n_iter)
        evals, V, residual = rr_residual(Q)
        return Q, evals, V, residual, rounds + 1

    _, evals, V, residual, _ = jax.lax.while_loop(
        cond, more, (Q1, evals0, V0, res0, jnp.zeros((), jnp.int32))
    )
    components = sign_flip(V.T)
    total_var = jnp.maximum(total_var, jnp.finfo(evals.dtype).tiny)
    ratio = evals / total_var
    singular_values = jnp.sqrt(jnp.maximum(evals, 0.0) * (wsum - 1.0))
    return mean, components, evals, ratio, singular_values, residual


# On CPU backends, above this column count the dense eigh leaves the jitted
# kernel for the host native runtime (spark_rapids_ml_tpu.native
# .eigh_descending: the C++ Jacobi kernel up to d=256, blocked LAPACK
# beyond, both with calSVD sign semantics) — the same split the reference
# uses when it runs raft eigDC on a single device after reducing partial
# covariances on the driver (RapidsRowMatrix.scala:59-89).  On TPU the
# XLA eigh (QDWH, MXU-friendly) stays on device: measured 0.31 s for
# d=3000 on v5e vs ~5-6 s for either host path PLUS the (D, D) covariance
# device->host transfer, so the whole fit stays in one jitted kernel.
HOST_EIGH_MIN_D = 128


def _is_cpu_backend(X: jax.Array) -> bool:
    try:
        return list(X.devices())[0].platform == "cpu"
    except Exception:
        return jax.default_backend() == "cpu"


def _mesh_of(X: jax.Array):
    """Mesh of a NamedSharding-backed array, else None (falls back to the
    monolithic GSPMD contraction)."""
    try:
        sharding = X.sharding
        return getattr(sharding, "mesh", None)
    except Exception:
        return None


def pca_fit(
    X: jax.Array, w: jax.Array, k: int, host_eigh: bool = None, mesh=None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Hybrid PCA fit: covariance on the mesh, then eigh on device (always
    on TPU; small D on CPU) or on the host native runtime (large D on CPU
    backends).  Returns numpy arrays
    (mean, components, explained_variance, ratio, singular_values)."""
    d = X.shape[1]
    if mesh is None:
        mesh = _mesh_of(X)
    if getattr(mesh, "shape", None) is not None and DATA_AXIS not in mesh.shape:
        mesh = None
    if host_eigh is None:
        host_eigh = d >= HOST_EIGH_MIN_D and _is_cpu_backend(X)
    if not host_eigh:
        # Small-k wide-D fits on accelerators use subspace iteration: the
        # QDWH eigh's COMPILE time at large D (~8 min at D=3000 on v5e) is
        # the whole cost of the dense path, while runtime is sub-second for
        # both.  Large k or modest D keep the dense eigh.  The kernel's
        # eigenpair residual guards accuracy: convergence depends on the
        # eigengap ratio (lambda_{k+p}/lambda_k)^n_iter, so near-isotropic
        # spectra can defeat the fixed iteration count — those fits pay the
        # exact-eigh compile instead of returning silently-wrong components.
        if not _is_cpu_backend(X) and k <= 32 and d >= 768:
            *out, residual = jax.device_get(
                pca_fit_subspace_kernel(X, w, k, mesh=mesh)
            )
            if float(residual) <= SUBSPACE_RESIDUAL_TOL:
                return tuple(out)  # type: ignore[return-value]
        # one batched device_get: five sequential np.asarray fetches each pay
        # the device-link round-trip latency
        return tuple(jax.device_get(pca_fit_kernel(X, w, k, mesh=mesh)))  # type: ignore[return-value]
    from .. import native

    wsum_d, mean_d, cov_d = covariance_kernel(X, w, mesh=mesh)
    # one batched explicit fetch (three implicit np.asarray/float coercions
    # each paid their own device round-trip and tripped the SRML_SANITIZE
    # transfer guard)
    wsum_h, mean_h, cov_h = jax.device_get((wsum_d, mean_d, cov_d))
    wsum = float(wsum_h)
    # the host eigh deliberately runs in f64 — fetched host arrays, not
    # device math (native.eigh_descending matches calSVD's f64 semantics)
    mean = mean_h.astype(np.float64)  # graftlint: disable=R5 (host-side eigh input)
    cov = cov_h.astype(np.float64)  # graftlint: disable=R5 (host-side eigh input)
    evals, comps = native.eigh_descending(cov)
    top = np.maximum(evals[:k], 0.0)
    total = max(evals.sum(), np.finfo(np.float64).tiny)  # graftlint: disable=R5 (host-side f64 epsilon)
    return (
        mean,
        comps[:k],
        evals[:k],
        evals[:k] / total,
        np.sqrt(top * (wsum - 1.0)),
    )


@jax.jit
def stream_moments_chunk_kernel(
    X: jax.Array, w: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One streamed chunk's weighted moments (wsum, xwsum, scatter) — the
    srml-stream PCA update kernel.  Single-device math over a pow2-bucketed
    chunk (pad rows carry zero weight): the reduction order is fixed by the
    chunk itself, never by the serving mesh, so accumulated streams are
    mesh-independent data the same way the IVF coarse quantizer is."""
    xw = X * w[:, None]
    return w.sum(), xw.sum(axis=0), exact_matmul(xw.T, X)


@jax.jit
def pca_transform_kernel(X: jax.Array, components: jax.Array) -> jax.Array:
    """Spark-parity projection: X @ PC^T *without* mean removal (Spark does not
    center at transform time; the reference adds the transformed mean back to
    cuML's centered output to match, feature.py:419-431 — we simply never
    subtract it)."""
    return exact_matmul(X, components.T)


@jax.jit
def lane_pca_transform_kernel(
    X: jax.Array, lanes: jax.Array, components: jax.Array
) -> jax.Array:
    """Multiplexed pca_transform_kernel (srml-lanes): components is the
    lane-stacked (L, K, D) buffer and row r projects against lane
    lanes[r]'s components — the exact contraction of the dedicated kernel,
    so on integer-exact data the two are bitwise equal."""
    return exact_gather_matmul(X, components, lanes)


