"""Lloyd's update pass over a feature-major table as one Pallas kernel
(ops/lloyd_tall_pass.py, PR 53): a tile of the table read once, cut into its
bfloat16 pieces once, the distances, the argmin and the weighted sums from
them.  Held here, through the Pallas interpreter, to a float64 numpy pass at
a tolerance that a form with one of the six partial products left out fails
(one is planted in each product), on values with full 24-bit mantissas and
float32 weights that are not 0/1; to its guarantees (ties to the lowest index,
padding rows of the centres and of the features take nothing, an emptied
centre keeps its place); to the tile its rule picks; to a four-device mesh;
and to its counter.  chip_smoke.py's `kernel_lloyd_tall` holds it to
ops/kmeans._tall_assign_stats on the chip."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import KMeans
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.ops import lloyd_tall_pass as lp
from spark_rapids_ml_tpu.ops.kmeans import _tall_assign_stats, lloyd_tall
from spark_rapids_ml_tpu.ops.tall import TallMatrix, padded_features
from spark_rapids_ml_tpu.parallel.mesh import col_sharding, data_sharding, get_mesh

TILE = 256
# what the sums may lie from float64's, relative to the largest: float32's own
# rounding of the sums reads 1e-7; a partial product left out reads 2e-6 and more
SOUND = 1e-6


def _case(k, cols, rows, seed=0):
    """A table, weights and centres whose every value has a full mantissa (uniform
    in [1, 2) scaled and signed): the table in blobs round the centres, so that
    every centre takes rows; a seventh of the weights zero, the others in [0.5, 2)."""
    rng = np.random.default_rng(seed + 1000 * k + cols)
    full = lambda shape, scale: (1.0 + rng.random(shape)) * scale * rng.choice([-1.0, 1.0], shape)
    d_pad = padded_features(cols)
    centres = np.zeros((k, d_pad), np.float32)
    centres[:, :cols] = full((k, cols), 2.0)
    xt = np.zeros((d_pad, rows), np.float32)
    xt[:cols] = (centres[rng.integers(0, k, rows), :cols] + 0.5 * full((rows, cols), 1.0)).T
    w = (0.5 + 1.5 * rng.random(rows)).astype(np.float32)
    w[rng.integers(0, 7, rows) == 0] = 0.0
    moved = centres.copy()
    moved[:, :cols] += full((k, cols), 0.125).astype(np.float32)
    return xt, w, moved


def _float64(xt, w, centres):
    """(sums, counts) of one pass in float64; ties of |m|^2 - 2 m.x to the lowest index."""
    X, c = xt.T.astype(np.float64), centres.astype(np.float64)
    a = ((c * c).sum(axis=1)[None, :] - 2.0 * X @ c.T).argmin(axis=1)
    hot = np.zeros((X.shape[0], c.shape[0]))
    hot[np.arange(X.shape[0]), a] = w
    return hot.T @ X, hot.sum(axis=0)


def _kernel(xt, w, centres, tile=TILE):
    return lp.pass_sums(jnp.asarray(xt), lp.weight_tiles(jnp.asarray(w), tile), jnp.asarray(centres), interpret=True)


def _gaps(got, want):
    return [float(np.abs(np.asarray(a, np.float64) - b).max() / np.abs(b).max()) for a, b in zip(got, want)]


@pytest.mark.parametrize("rows", [3 * TILE, 3 * TILE + 77], ids=["whole_tiles", "rows_over"])
@pytest.mark.parametrize("cols", [3, 30, 64])
@pytest.mark.parametrize("k", [2, 20, 130])
def test_kernel_is_the_float64_pass_over_the_whole_tiles(k, cols, rows):
    xt, w, centres = _case(k, cols, rows)
    done = rows // TILE * TILE
    got = _kernel(xt, w, centres)
    assert got[0].shape == (k, padded_features(cols)) and got[1].shape == (k,)
    gaps = _gaps(got, _float64(xt[:, :done], w[:done], centres))
    assert max(gaps) < SOUND, gaps
    # the feature rows past the table's columns take nothing
    assert not np.asarray(got[0])[:, cols:].any()


def _centres_without(group):
    """centre_stack with one block of its (2 kp, 4 d) form zeroed: (0, 3) is
    m_mid x_hi, (1, 0) m_lo x_hi."""
    real = lp.centre_stack

    def planted(centres):
        kp, d = centres.shape
        stack = real(centres)
        return stack.at[group[0] * kp:(group[0] + 1) * kp, group[1] * d:(group[1] + 1) * d].set(0)

    return planted


def _blocks_without(group):
    """six_blocks with one of the six left out: (0, 1) is hot_hi x_mid."""
    def planted(acc, kp, d):
        g = lambda i, j: acc[i * kp:(i + 1) * kp, j * d:(j + 1) * d]
        return sum(g(i, j) for i, j in [(2, 0), (1, 1), (0, 2), (1, 0), (0, 1), (0, 0)] if (i, j) != group)

    return planted


@pytest.mark.parametrize("k,cols", [(2, 3), (20, 30), (130, 64)])
@pytest.mark.parametrize("group", [(0, 1), (1, 0)], ids=["hi_mid", "mid_hi"])
def test_the_sums_short_of_a_partial_product_are_not_sound(monkeypatch, k, cols, group):
    xt, w, centres = _case(k, cols, 2 * TILE)
    want = _float64(xt, w, centres)
    monkeypatch.setattr(lp, "six_blocks", _blocks_without(group))
    lp.pass_sums.clear_cache()
    try:
        gaps = _gaps(_kernel(xt, w, centres), want)
    finally:
        monkeypatch.undo()
        lp.pass_sums.clear_cache()
    assert gaps[0] > 2 * SOUND, gaps
    assert max(_gaps(_kernel(xt, w, centres), want)) < SOUND


@pytest.mark.parametrize("group", [(0, 1), (0, 3)], ids=["hi_mid", "mid_hi"])
def test_the_distances_short_of_a_partial_product_choose_other_centres(monkeypatch, group):
    """Two centres far apart and rows by the plane between them, 0.0025 of d2 to
    one side or the other: a hundred times float32's rounding of d2 (some 300),
    a fiftieth of what a partial product of the first order carries.  Sound,
    every row goes where float64 sends it; short of one, rows change sides."""
    rng = np.random.default_rng(5)
    cols, rows = 30, 4 * TILE
    full = lambda n: (1.0 + rng.random(n)) * 2.0 * rng.choice([-1.0, 1.0], n)
    centres = np.zeros((2, 32), np.float32)
    centres[0, :cols], centres[1, :cols] = full(cols), full(cols)
    c64 = centres.astype(np.float64)
    apart = np.linalg.norm(c64[1] - c64[0])
    mid, way = (c64[0] + c64[1]) / 2, (c64[1] - c64[0]) / apart
    along = np.zeros((rows, 32))
    along[:, :cols] = rng.normal(size=(rows, cols)) * 2.0
    along -= np.outer(along @ way, way)
    side = rng.choice([-1.0, 1.0], rows)
    xt = (mid + along + np.outer(side * 0.0025 / apart, way)).T.astype(np.float32)
    w = np.ones(rows, np.float32)
    want = _float64(xt, w, centres)
    assert np.array_equal(want[1], [(side < 0).sum(), (side > 0).sum()])
    monkeypatch.setattr(lp, "centre_stack", _centres_without(group))
    lp.pass_sums.clear_cache()
    try:
        short = np.asarray(_kernel(xt, w, centres)[1])
    finally:
        monkeypatch.undo()
        lp.pass_sums.clear_cache()
    assert np.array_equal(_kernel(xt, w, centres)[1], want[1])
    assert np.abs(short - want[1]).max() >= 0.02 * rows, (short, want[1])


def test_an_exact_tie_goes_to_the_lowest_index():
    """Whole numbers, so every distance is exact: the rows at 0 are as far from
    centre 0 as from centres 1 and 2, which are one point."""
    rows = 2 * TILE
    xt = np.zeros((8, rows), np.float32)
    xt[0, : rows // 4], xt[0, rows // 4 : rows // 2] = -4.0, 4.0
    xt[1] = np.arange(rows) % 3 - 1
    centres = np.zeros((3, 8), np.float32)
    centres[:, 0] = [-2.0, 2.0, 2.0]
    sums, counts = _kernel(xt, np.ones(rows, np.float32), centres)
    assert np.array_equal(counts, [3 * rows // 4, rows // 4, 0])
    want = _float64(xt, np.ones(rows), centres)
    assert np.array_equal(sums, want[0]) and np.array_equal(counts, want[1])


def test_the_padding_centre_rows_are_never_chosen():
    """k = 3 lies in 8 rows: rows far from every centre (and nearer the origin,
    where the padding rows' zero pieces lie) still go to one of the three."""
    xt, w, centres = _case(3, 30, 2 * TILE)
    xt *= 0.01
    centres[:, :30] += 50.0
    assert lp.centre_rows(3) == 8
    sums, counts = _kernel(xt, w, centres)
    want = _float64(xt, w, centres)
    assert counts.shape == (3,) and float(counts.sum()) == pytest.approx(float(w.sum()), rel=1e-6)
    assert max(_gaps((sums, counts), want)) < SOUND


def _solve(X, w, centres0, iters, chunk, devices=1):
    mesh = get_mesh(devices)
    n, d = X.shape
    xt = np.zeros((padded_features(d), n), np.float32)
    xt[:d] = X.T
    table = TallMatrix(jax.device_put(xt, col_sharding(mesh)), d)
    out = lloyd_tall(table, jax.device_put(np.asarray(w, np.float32), data_sharding(mesh)),
                     jnp.asarray(centres0, jnp.float32), mesh, iters, 0.0, chunk)
    return np.asarray(out[0]), int(out[1]), float(out[2])


def _blobs(n, d, seed=0, k_true=5, box=3.0):
    rng = np.random.default_rng(seed)
    centres = rng.uniform(-box, box, (k_true, d))
    return (centres[rng.integers(0, k_true, n)] + rng.normal(size=(n, d))).astype(np.float32)


def test_an_emptied_centre_keeps_its_place_through_the_kernel():
    X = _blobs(3 * TILE + 40, 3, seed=4)
    centres0 = np.vstack([X[:3], [[500.0, -500.0, 500.0]]]).astype(np.float32)
    assert lp.row_tile(4, 8, len(X), TILE) == TILE
    centres, iters, _ = _solve(X, np.ones(len(X)), centres0, iters=4, chunk=TILE)
    assert iters == 4 and np.array_equal(centres[3], centres0[3])
    assert not np.array_equal(centres[:3], centres0[:3])


@pytest.mark.parametrize("rows", [4 * 3 * 128, 4 * 3 * 128 + 4 * 50], ids=["whole_tiles", "rows_over"])
def test_four_devices_give_the_one_device_pass(rows):
    X = _blobs(rows, 30, seed=6)
    w = np.random.default_rng(2).uniform(0.5, 2.0, rows)
    one, four = _solve(X, w, X[:20], 3, chunk=128), _solve(X, w, X[:20], 3, chunk=128, devices=4)
    np.testing.assert_allclose(four[0], one[0], rtol=2e-5, atol=2e-5)
    assert four[1] == one[1] == 3 and four[2] == pytest.approx(one[2], rel=1e-5)


def test_the_kernel_is_its_xla_twin():
    """ops/kmeans._tall_assign_stats over the same rows: the same centres chosen
    (|x|^2 moves no choice on rows in blobs), the sums to float32's rounding."""
    xt, w, centres = _case(20, 30, 4 * TILE)
    x_norm = (xt * xt).sum(axis=0)
    twin = _tall_assign_stats(jnp.asarray(xt), jnp.asarray(w), jnp.asarray(centres), TILE, jnp.asarray(x_norm))
    got = _kernel(xt, w, centres)
    np.testing.assert_allclose(got[1], twin[1], rtol=1e-6)
    np.testing.assert_allclose(got[0], twin[0], rtol=2e-6, atol=2e-5)


def test_no_dot_of_the_kernel_asks_for_a_precision_and_every_dot_takes_bfloat16():
    xt, w, centres = _case(20, 30, 2 * TILE)
    jaxpr = jax.make_jaxpr(lambda *a: lp.pass_sums(*a, interpret=True))(
        jnp.asarray(xt), lp.weight_tiles(jnp.asarray(w), TILE), jnp.asarray(centres))
    dots = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "dot_general":
                dots.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    # two dots a trip of the kernel's loop, whatever lane tiles the trip takes as one array
    assert len(dots) == 2 and lp.lane_group(lp.centre_rows(20), 32, TILE // 128) == 2 and lp.lane_group(24, 32, 256) == 32
    for eqn in dots:
        assert eqn.params["precision"] is None and eqn.params["preferred_element_type"] == jnp.float32
        assert all(v.aval.dtype == jnp.bfloat16 for v in eqn.invars)


def test_the_stacks_hold_the_pieces_and_the_pieces_are_the_value():
    rng = np.random.default_rng(1)
    m = ((1.0 + rng.random((8, 16))) * rng.choice([-3.0, 3.0], (8, 16))).astype(np.float32)
    stack = np.asarray(lp.centre_stack(jnp.asarray(m)).astype(jnp.float32))
    assert stack.shape == (16, 64)
    hi, mid, lo = stack[:8, :16], stack[:8, 48:], stack[8:, :16]
    assert np.array_equal(stack[:8, 16:32], hi) and np.array_equal(stack[:8, 32:48], hi)
    assert np.array_equal(stack[8:, 16:32], mid) and not stack[8:, 32:].any()
    assert np.array_equal(hi + mid + lo, m)
    t = np.asarray(lp.table_stack(jnp.asarray(m)).astype(jnp.float32))
    assert t.shape == (32, 16) and np.array_equal(t[:8], hi) and np.array_equal(t[8:16], mid)
    assert np.array_equal(t[16:24], lo) and np.array_equal(t[24:], hi)


@pytest.mark.parametrize(
    "k,d_pad,n_loc,chunk",
    [(20, 32, 25_000_000, 32768), (1000, 32, 25_000_000, 32768), (1000, 32, 25_000_000, 1 << 20),
     (20, 32, 25_000_000, 1 << 20), (20, 32, 1000, 32768), (20, 32, 100, 32768), (8, 3000, 1 << 20, 32768),
     (20, 32, 1 << 20, 100)],
    ids=["cell", "k1000", "k1000_wide_chunk", "wide_chunk", "small_shard", "no_lane_tile", "wide_table", "small_chunk"],
)
def test_the_tile_rule_fits_its_budget_and_never_passes_the_chunk(k, d_pad, n_loc, chunk):
    tile = lp.row_tile(k, d_pad, n_loc, chunk)
    assert tile % 128 == 0 and tile <= min(chunk, n_loc)
    if min(chunk, n_loc) < 128:
        assert tile == 0
        return
    kp = lp.centre_rows(k)
    held = (2 * (d_pad + 1) * 4 * tile + lp._LIVE_ARRAYS * max(kp, lp._TRIP_ROWS) * 128 * 4 + 6 * kp * d_pad * 4
            + lp._STACK_BYTES)
    assert 0 < tile and held <= lp._VMEM_BUDGET < lp._VMEM_LIMIT
    if tile >= 4096:
        assert tile % 4096 == 0
    group = lp.lane_group(kp, d_pad, tile // 128)
    assert (tile // 128) % group == 0 and group * max(kp, d_pad) <= max(lp._TRIP_ROWS, kp, d_pad)
    # a larger k never takes a larger tile
    assert lp.row_tile(4 * k, d_pad, n_loc, chunk) <= tile


def test_the_rule_gives_the_cell_a_chunk_and_more_centres_or_features_less_of_a_wide_one():
    """A trip's arrays are bounded (lane_group), so k = 20 and k = 1000 plan with the
    same room; past the bound the centres' rows take the tile's."""
    assert lp.row_tile(20, 32, 25_000_000, 32768) == lp.row_tile(1000, 32, 25_000_000, 32768) == 32768
    wide = lp.row_tile(20, 32, 25_000_000, 1 << 20)
    assert 0 < lp.row_tile(2000, 32, 25_000_000, 1 << 20) < lp.row_tile(1000, 32, 25_000_000, 1 << 20) == wide < 1 << 20
    assert 0 < lp.row_tile(20, 64, 25_000_000, 1 << 20) < wide
    assert [lp.lane_group(lp.centre_rows(k), 32, 256) for k in (20, 130, 1000)] == [32, 4, 1]


def test_the_kernel_is_right_at_a_thousand_centres_with_the_tile_its_rule_picks():
    k, cols, rows = 1000, 30, 600
    tile = lp.row_tile(k, 32, rows, 32768)
    assert tile == 512
    xt, w, centres = _case(k, cols, rows, seed=3)
    gaps = _gaps(_kernel(xt, w, centres, tile), _float64(xt[:, :tile], w[:tile], centres))
    assert max(gaps) < SOUND, gaps


def _fit_counters(X, **kw):
    n, d = X.shape
    xt = np.zeros((padded_features(d), n), X.dtype)
    xt[:d] = X.T
    est = KMeans(k=4, maxIter=2, tol=0.0, initMode="random", seed=3, num_workers=1, **kw)
    model = est.fit(DataFrame.from_device(TallMatrix(jnp.asarray(xt), d)))
    return model.fit_telemetry().counters


def test_a_fit_whose_passes_take_the_kernel_counts_one_kernel_fit():
    counters = _fit_counters(_blobs(700, 3, seed=2), max_samples_per_batch=256)
    assert counters["lloyd.tall_fits"] == 1 and counters["lloyd.tall_kernel_fits"] == 1


def test_a_fit_of_fewer_rows_than_a_lane_tile_counts_none():
    counters = _fit_counters(_blobs(100, 3, seed=2))
    assert counters["lloyd.tall_fits"] == 1 and "lloyd.tall_kernel_fits" not in counters


def test_takes_follows_the_dtype_the_shard_and_the_chunk():
    table = lambda n, dtype=jnp.float32: TallMatrix(jax.ShapeDtypeStruct((32, n), dtype), 30)
    assert lp.takes(table(1000), 20, 32768) and lp.takes(table(512), 20, 32768, devices=4)
    assert not lp.takes(table(500), 20, 32768, devices=4)       # 125 rows a device
    assert not lp.takes(table(1000), 20, 100)                   # a chunk under a lane tile
    assert not lp.takes(table(1000, jnp.float64), 20, 32768)


_IMPORT_CHECK = """
import sys
import jax
from spark_rapids_ml_tpu.ops import lloyd_tall_pass as lp
gpu = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"
first = {first!r}
if first:
    from jax.experimental import pallas
pl, pltpu = lp._pallas()
assert pl is sys.modules["jax.experimental.pallas"] and pltpu is sys.modules["jax.experimental.pallas.tpu"]
assert gpu not in sys.modules or sys.modules[gpu] is not None, "the refusal was left standing"
assert ("jax.experimental.mosaic.gpu" in sys.modules) == first, sorted(m for m in sys.modules if "mosaic" in m)
assert lp._pallas() == (pl, pltpu)
"""


@pytest.mark.parametrize("first", [False, True], ids=["not_imported_yet", "imported_before"])
def test_pallas_comes_without_mosaic_gpus_interpreter_unless_it_was_there(first):
    """In a process of its own: the kernel's import of Pallas leaves Mosaic GPU's
    interpreter (and the half second of modules behind it) out, refuses nothing
    afterwards, and leaves an import that came before it as it is."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", _IMPORT_CHECK.format(first=first)], env=env, capture_output=True, text=True,
                          cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


_AHEAD_CHECK = """
import sys, threading, time
import spark_rapids_ml_tpu
from spark_rapids_ml_tpu import KMeans
import numpy as np
from spark_rapids_ml_tpu.dataframe import DataFrame
KMeans(k=2, maxIter=2, initMode="random", seed=1, num_workers=1).fit(
    DataFrame.from_device(np.random.default_rng(0).normal(size=(64, 3)).astype(np.float32)))
assert "spark_rapids_ml_tpu.ops.tall" not in sys.modules, "the package, or a fit of a row-major device frame, imported the feature-major table"
assert "jax.experimental.pallas" not in sys.modules
assert not [t for t in threading.enumerate() if t.name == "srml-pallas-import"]
from spark_rapids_ml_tpu.ops.tall import TallMatrix
started = [t for t in threading.enumerate() if t.name == "srml-pallas-import"]
assert len(started) <= 1 and all(t.daemon for t in started)
for t in started:
    t.join(60)
assert "jax.experimental.pallas.tpu" in sys.modules, "the thread ended without Pallas"
assert "jax.experimental.mosaic.gpu" not in sys.modules
assert not [t for t in threading.enumerate() if t.name == "srml-pallas-import"], "the thread outlived its import"
"""


def test_importing_the_table_imports_pallas_ahead_on_a_thread_that_ends_and_a_row_major_fit_does_neither():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", _AHEAD_CHECK], env=env, capture_output=True, text=True,
                          cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=180)
    assert done.returncode == 0, done.stderr[-2000:]
