"""The deep histogram kernel streams only the tiles a tree's segments own
(ops/forest_hist.node_histograms_segmented, ops/forest_mxu._deep_layout): a row
that weighs nothing in a tree sorts behind the tree's segments, the tiles there
are stray, and what they hold never enters a sum.  CPU, seeded, small; the
kernel runs through the Pallas interpreter (compiled by Mosaic with
SRML_TPU_TESTS=1 on a chip, as tests/test_forest_mxu.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import profiling
from spark_rapids_ml_tpu.ops import forest_mxu
from spark_rapids_ml_tpu.ops.forest import (
    bin_features,
    bootstrap_weights,
    compute_bin_edges,
    grow_forest,
)
from spark_rapids_ml_tpu.ops.forest_hist import (
    _F_BLOCK,
    _ROW_TILE,
    _ROW_TILE_DEEP,
    node_histograms_segmented,
    tile_feature_rows,
)

KERNEL_INTERPRET = jax.devices()[0].platform != "tpu"
TILE = _ROW_TILE_DEEP
BINS = 128


def _segment_sums(bins, node, stats, tile_seg, n_segs, nodes):
    """Plain float64 sums of a tree's kept tiles: (n_segs, F, nodes * S, BINS)."""
    F, S = bins.shape[0], stats.shape[0]
    H = np.zeros((n_segs, F, nodes, S, BINS))
    for k, seg in enumerate(tile_seg):
        if seg >= n_segs:
            continue
        rows = slice(k * TILE, (k + 1) * TILE)
        for f in range(F):
            for s in range(S):
                np.add.at(H[seg, f, :, s], (node[rows], bins[f, rows]), stats[s, rows])
    return H.reshape(n_segs, F, nodes * S, BINS)


# a tree's tile map; segment 3 is the stray one
LAYOUTS = {
    "no_stray_tile": [0, 0, 1, 2, 2, 2],
    "a_few_stray_tiles": [0, 1, 1, 2, 3, 3],
    "a_tile_a_segment": [0, 1, 2, 3, 3, 3],
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("nodes", [1, 4])
def test_stray_tiles_are_not_streamed_and_the_last_kept_block_survives_them(layout, nodes):
    """Three trees, the window takes the last two (t0 = 1): the first of them
    in the parametrised layout, the second always with stray tiles, so a tree
    whose last tile is kept is followed by one that skips.  Every stray tile is
    poison (NaN weights, bin 127, a live node id): the kept segments' sums
    come out to the bit, the LAST segment's too, whose block is resident when
    the first stray tile's step names another segment."""
    rng = np.random.default_rng(len(layout) + nodes)
    n_segs, S, T = 3, 2, 3
    maps = np.array([LAYOUTS["a_few_stray_tiles"], LAYOUTS[layout], LAYOUTS["a_few_stray_tiles"]], np.int32)
    n2 = maps.shape[1] * TILE
    bins = rng.integers(0, BINS, (T, _F_BLOCK, n2)).astype(np.int8)
    node = rng.integers(0, nodes + 1, (T, n2)).astype(np.int32)      # == nodes: masked out
    stats = rng.poisson(1.0, (T, S, n2)).astype(np.float32)
    stray = np.repeat(maps == n_segs, TILE, axis=1)
    bins[np.broadcast_to(stray[:, None, :], bins.shape)] = 127
    node[stray] = 0
    stats[np.broadcast_to(stray[:, None, :], stats.shape)] = np.nan

    got = np.asarray(node_histograms_segmented(
        jnp.asarray(bins), jnp.asarray(node)[:, None, :], jnp.asarray(stats), jnp.asarray(maps[1:].reshape(-1)),
        jnp.asarray(1, jnp.int32), t_chunk=2, n_segs=n_segs, nodes=nodes, s_dim=S, n_bins=BINS, f_pad=_F_BLOCK,
        interpret=KERNEL_INTERPRET,
    ))
    assert got.shape[0] == 2 * n_segs                                # no block for the stray tiles
    got = got.reshape((2, n_segs) + got.shape[1:])[..., : nodes * S, :]
    for t in (1, 2):
        live = node[t] < nodes
        want = _segment_sums(bins[t], np.where(live, node[t], 0), np.where(live & ~stray[t], stats[t], 0.0), maps[t], n_segs, nodes)
        assert want[-1].sum() > 0
        np.testing.assert_array_equal(got[t - 1].astype(np.float64), want)


def _layout(rel, w, nb):
    n2 = forest_mxu._deep_width(rel.shape[1], nb)
    out = forest_mxu._deep_layout(jnp.asarray(rel), jnp.asarray(w), n_buckets=nb, n2=n2)
    return [np.asarray(a) for a in out], n2


def test_deep_layout_sorts_rows_that_weigh_nothing_behind_the_segments():
    """Keys, counts, tiles: a weight-0 row takes the stray key; a segment counts
    its weighted rows, owns at least one tile (bucket 2 of tree 0 has rows, all
    out of bag: one filler tile) and whole tiles; the kept tiles are the
    segments' and the stray ones the last."""
    rng = np.random.default_rng(3)
    T, n_pad, nb = 2, 2 * _ROW_TILE, 4
    rel = rng.integers(0, nb + 2, (T, n_pad)).astype(np.int32)     # >= nb: stopped in the shallow phase
    w = rng.poisson(1.0, (T, n_pad)).astype(np.float32)
    w[0, rel[0] == 2] = 0.0
    (keys, dkeys, tile_seg, kept), n2 = _layout(rel, w, nb)

    np.testing.assert_array_equal(keys, np.where(w > 0, np.minimum(rel, nb), nb))
    assert (keys[w == 0] == nb).all() and (keys[0] != 2).all()
    for t in range(T):
        counts = np.bincount(keys[t], minlength=nb + 1)[:nb]
        np.testing.assert_array_equal(counts, [((rel[t] == b) & (w[t] > 0)).sum() for b in range(nb)])
        every = np.concatenate([keys[t], dkeys[t]])
        assert every.shape == (n2,)
        full = np.bincount(every, minlength=nb + 1)                  # rows and filler a segment
        aligned = -(-np.maximum(counts, 1) // TILE) * TILE
        np.testing.assert_array_equal(full[:nb], aligned)
        assert aligned.sum() <= n2 and (aligned >= TILE).all()
        seg_end = np.cumsum(aligned)
        assert kept[t] == seg_end[-1] // TILE
        # the sorted order is by key: tile k lies in the segment that covers row k * TILE
        np.testing.assert_array_equal(tile_seg[t], np.sort(every)[::TILE])
        assert (tile_seg[t][: kept[t]] < nb).all() and (tile_seg[t][kept[t]:] == nb).all()
    assert np.bincount(tile_seg[0], minlength=nb)[2] == 1            # all out of bag: its one filler tile
    assert kept[0] < n2 // TILE


def test_deep_layout_without_weights_of_zero_moves_nothing_but_the_filler():
    """bootstrap=False, no padding: every row keeps its segment's key."""
    rng = np.random.default_rng(4)
    rel = rng.integers(0, 4, (1, _ROW_TILE)).astype(np.int32)
    (keys, _dkeys, tile_seg, kept), n2 = _layout(rel, np.ones(rel.shape, np.float32), 4)
    np.testing.assert_array_equal(keys, rel)
    counts = np.bincount(rel[0], minlength=4)
    assert kept[0] == (-(-counts // TILE)).sum() == (tile_seg[0] < 4).sum() < n2 // TILE


def _deep_problem(seed, classes=8, depth=6):
    """8 classes leave the shallow phase 5 levels, so depth 6 splits level 5 in
    the deep phase (32 segments a tree) and totals level 6 there."""
    rng = np.random.default_rng(seed)
    N, D, B, T = _ROW_TILE, 8, 16, 3
    X = rng.standard_normal((N, D)).astype(np.float32)
    score = X @ rng.standard_normal(D) + 0.3 * rng.standard_normal(N)
    y = np.digitize(score, np.quantile(score, np.linspace(0, 1, classes + 1)[1:-1])).astype(np.float32)
    edges = compute_bin_edges(X, B)
    Xb = np.asarray(bin_features(jnp.asarray(X), jnp.asarray(edges)))
    w = np.asarray(bootstrap_weights(np.array([seed, T, N, 0], np.int64))).astype(np.float32)
    base = np.stack([y == c for c in range(classes)]).astype(np.float32)
    kwargs = dict(max_depth=depth, n_bins=B, kind="gini", max_features=D, min_samples_leaf=1.0, min_impurity_decrease=0.0, seed=7)
    return Xb, y, w, base, edges, kwargs


@pytest.mark.parametrize("seed", [1, 3])
def test_deep_fit_with_bootstrap_weights_is_the_scatter_builders_forest(seed):
    """Poisson weights in place of ones: a third of a tree's rows go stray, and
    the forest is the one the scatter builder grows from the same weights,
    array for array (seeds on which no two edges of a node tie in gain: there
    the builders' float32 gains may order either way, before this layout too)."""
    Xb, y, w, base, edges, kwargs = _deep_problem(seed)
    assert 0.3 < (w == 0).mean() < 0.45
    got = forest_mxu.grow_forest_mxu(
        jnp.asarray(Xb.T.astype(np.int8)), jnp.asarray(base), jnp.asarray(w), None, edges,
        y_vals=jnp.asarray(y), interpret=KERNEL_INTERPRET, **kwargs,
    )
    stats_t = jnp.asarray(w[:, :, None] * base.T[None, :, :])
    want = [np.asarray(a) for a in grow_forest(jnp.asarray(Xb), stats_t, edges, **kwargs)]
    assert (want[0][:, 2**5 - 1: 2**6 - 1] >= 0).any()              # splits at the deep level
    for got_a, want_a in zip(got[:4], want[:4]):                     # features, thresholds, leaf values, node weights
        np.testing.assert_array_equal(got_a, want_a)
    held = want[3] > 0                                               # an empty node's impurity is either's convention
    np.testing.assert_allclose(got[4][held], want[4][held], atol=1e-6)


def test_deep_tile_counters_are_the_layouts_own_counts():
    """forest.deep_tiles: trees x tiles x deep histogram levels, static;
    forest.deep_tiles_kept: the fetched per-tree counts x those levels."""
    Xb, y, w, base, edges, kwargs = _deep_problem(6)
    before = profiling.counters("forest.")
    (buf, kept), plan = forest_mxu.grow_forest_mxu_device(
        tile_feature_rows(jnp.asarray(Xb.T.astype(np.int8))), jnp.asarray(base), jnp.asarray(w), None,
        y_vals=jnp.asarray(y), interpret=KERNEL_INTERPRET, **kwargs,
    )
    kept_h = np.asarray(kept)
    forest_mxu.pack_forest(jax.device_get((buf, kept)), plan, edges)
    moved = profiling.counter_deltas(before, "forest.")
    T, n_pad = w.shape
    nb, levels = 2**plan.deep_level, kwargs["max_depth"] - plan.deep_level
    n_tiles = forest_mxu._deep_width(n_pad, nb) // TILE
    assert (plan.deep_level, levels) == (5, 1)
    assert moved["forest.deep_tiles"] == T * n_tiles * levels
    assert moved["forest.deep_tiles_kept"] == int(kept_h.sum()) * levels
    assert (kept_h >= nb).all() and (kept_h < n_tiles).all()
    # a tree keeps its weighted rows' tiles and under one of filler a segment
    rows = (w > 0).sum(axis=1)
    assert (kept_h * TILE >= rows).all() and (kept_h * TILE < rows + nb * TILE + TILE).all()
