"""The chunk scan of ops/linalg._local_moments computes one triangle of X'WX (PR 31).

A chunk's scatter is taken as gram_panels(d) column panels, each against the
columns from its own start on, and the lower triangle is mirrored in once after
the scan.  Held here: the scatter (and every other moment) against the weighted
product in float64, at one panel, at the panel width and just over it, at a
ragged width and at the benchmark cell's, on rows that are no multiple of the
chunk, with weights that hold zeros, with and without labels, on one device and
over a mesh; that the result equals its transpose to the bit once there is more
than one panel and equals the whole product's scan to the bit at one; that the
lowered module holds the panels' products and no other; and that a fault planted
through linalg.exact_matmul still reaches them (what the benchmark cell's
fault_gram control rests on)."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import control, harness
from spark_rapids_ml_tpu.ops import glm, linalg
from spark_rapids_ml_tpu.ops.linalg import GRAM_PANEL_WIDTH as WIDTH, gram_panels
from spark_rapids_ml_tpu.parallel.mesh import get_mesh

ROWS, CHUNK = 300, 128          # two whole chunks and 44 rows left over (before PR 50: a third chunk, clamped, 84 of its rows seen before)
WIDTHS = [8, WIDTH, WIDTH + 1, 2 * WIDTH + 37, 3000]
# of the whole product's d^2 outputs, what the panels may compute at d = 3000 (0.584 at 512, 0.667 at 1024)
AREA_SHARE = 0.60 if WIDTH <= 512 else 0.67


def _rows(d, seed=0):
    rng = np.random.default_rng(seed + d)
    X = rng.standard_normal((ROWS, d)).astype(np.float32)
    w = rng.uniform(0.5, 2.0, ROWS).astype(np.float32)
    w[::7] = 0.0
    y = rng.standard_normal(ROWS).astype(np.float32)
    return X, w, y


def _whole_product_scan(X, w):
    """The scatter as the walk had it before the panels: one product a block, over the blocks
    the walk takes since PR 50 (the whole chunks where they lie, then the rows left over alone).
    Not jitted: the case calls _local_moments eagerly, and the left-over block's product
    compiled alone and compiled inside a larger module differ in the last bit on the CPU."""
    n_full, tail = divmod(X.shape[0], CHUNK)

    def block(G, start, size):
        xb = jax.lax.dynamic_slice_in_dim(X, start, size)
        wb = jax.lax.dynamic_slice_in_dim(w, start, size)
        return G + linalg.exact_matmul((xb * wb[:, None]).T, xb)

    G = jnp.zeros((X.shape[1],) * 2, X.dtype)
    if n_full:
        G = jax.lax.scan(lambda G, i: (block(G, i * CHUNK, CHUNK), None), G, jnp.arange(n_full, dtype=jnp.int32))[0]
    return block(G, n_full * CHUNK, tail) if tail else G


def test_the_panel_rule():
    assert [gram_panels(d) for d in (0, 1, 8, WIDTH, WIDTH + 1, 2 * WIDTH, 2 * WIDTH + 37)] == [1, 1, 1, 1, 2, 2, 3]
    assert WIDTH % 128 == 0 and 2 <= gram_panels(3000) <= 12


@pytest.mark.parametrize("n_dev", [1, 2])
@pytest.mark.parametrize("with_y", [True, False], ids=["labels", "no_labels"])
@pytest.mark.parametrize("d", WIDTHS)
def test_the_panel_scan_is_the_weighted_product(d, with_y, n_dev):
    X, w, y = _rows(d)
    Xd, wd, yd = jnp.asarray(X), jnp.asarray(w), jnp.asarray(y)
    if n_dev == 1:
        out = linalg._local_moments(Xd, wd, CHUNK, y_loc=yd if with_y else None)
        wsum, xwsum, G = out[:3]
        rest = out[3:]
    elif with_y:
        s = glm.linreg_sufficient_stats(Xd, yd, wd, mesh=get_mesh(2), chunk=CHUNK)
        wsum, xwsum, G, rest = s.wsum, s.x_mean * s.wsum, s.G, (s.y_mean * s.wsum, s.c, s.y2)
    else:
        wsum, mean, G = linalg._sharded_moments(Xd, wd, get_mesh(2), CHUNK)
        xwsum, rest = mean * wsum, ()
    wsum, xwsum, G, rest = jax.device_get((wsum, xwsum, G, rest))
    X64, w64, y64 = X.astype(np.float64), w.astype(np.float64), y.astype(np.float64)
    want = (X64 * w64[:, None]).T @ X64
    assert G.shape == (d, d) and len(rest) == (3 if with_y else 0)
    assert np.abs(G - want).max() <= 1e-6 * np.abs(want).max()
    assert wsum == pytest.approx(w64.sum(), rel=1e-6)
    np.testing.assert_allclose(xwsum, (X64 * w64[:, None]).sum(0), atol=2e-5 * ROWS ** 0.5)
    if with_y:
        assert rest[0] == pytest.approx((y64 * w64).sum(), abs=1e-4)
        np.testing.assert_allclose(rest[1], (X64 * w64[:, None]).T @ y64, atol=2e-5 * ROWS ** 0.5)
        assert rest[2] == pytest.approx((y64 * y64 * w64).sum(), rel=1e-6)
    if gram_panels(d) > 1:
        assert np.array_equal(G, G.T)       # the lower triangle IS the upper one
    elif n_dev == 1:
        # one panel is the whole product, as the scan had it before there were panels
        assert np.array_equal(G, np.asarray(_whole_product_scan(Xd, wd)))


def test_an_empty_shard_has_zero_moments():
    out = linalg._local_moments(jnp.zeros((0, WIDTH + 5), jnp.float32), jnp.zeros((0,), jnp.float32), CHUNK)
    assert out[2].shape == (WIDTH + 5,) * 2 and not np.asarray(out[2]).any()


def _products(d, mesh_devices=1):
    """(text, [(rows, cols) of every matrix product]) of the lowered statistics pass."""
    X = jax.ShapeDtypeStruct((4096, d), jnp.float32)
    v = jax.ShapeDtypeStruct((4096,), jnp.float32)
    text = glm.linreg_sufficient_stats.lower(X, v, v, mesh=get_mesh(mesh_devices)).as_text(debug_info=True)
    shapes = re.findall(r"stablehlo\.dot_general.*->\s*tensor<([0-9x]+)xf32>", text)
    return text, [tuple(int(n) for n in s.split("x")) for s in shapes if "x" in s]


def test_the_lowered_module_holds_one_triangles_products():
    text, dots = _products(3000)
    assert "linreg.gram" in text
    assert len(dots) == gram_panels(3000)
    assert all(rows <= WIDTH and cols == 3000 - i * WIDTH for i, (rows, cols) in enumerate(dots))
    assert 0.5 * 3000 ** 2 < sum(r * c for r, c in dots) <= AREA_SHARE * 3000 ** 2
    text, dots = _products(8, mesh_devices=2)
    assert "linreg.gram" in text and dots == [(8, 8)]


def test_a_fault_planted_in_exact_matmul_reaches_the_panels():
    """chipbench's fault_gram replaces linalg.exact_matmul and clears the jitted
    pass's cache; at a width of more than one panel the program's fit on that
    one-pass Gram still fails the limit the fault is held against."""
    bench = harness.load_benchmark()
    small = {"data": {"rows_per_chip": 4096, "cols": WIDTH + 64, "informative": 4}}
    r = control.readings(bench, dict(harness.find_cell(bench, "linreg_enet_fit")), 2**31 + 31, 0.2, "fault_gram", small)
    assert all(c["ok"] for c in r["sound"]), r["sound"]
    held = {c["name"]: c for c in r["control"]}["small_coef_gap"]
    assert not held["ok"] and held["value"] > held["limit"], held
