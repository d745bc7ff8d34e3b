"""The deep phase's payload sorts (ops/forest_mxu._sort_part) carry the key and the
payloads they are handed and nothing else: no stability operand, because the combined
key is unique over a tree's positions, and payloads that share a key share a sort.
Held to the form they replaced (a stable sort a payload) element for element, on keys
with repeats, strays and filler; the uniqueness asserted on a layout's data; the
operand list read from the lowered module; a deep classifier and a deep regressor grown
twice, once with the old form in _sort_part's place, equal to the bit; the counters.

CPU, seeded, small: the kernels through the Pallas interpreter."""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu import RandomForestClassifier, RandomForestRegressor, profiling
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.models import random_forest as program
from spark_rapids_ml_tpu.ops import forest_hist, forest_mxu
from spark_rapids_ml_tpu.ops.precompile import global_precompiler

NB, T, N_PAD, BINS = 8, 3, 2048, 16
N2 = forest_mxu._deep_width(N_PAD, NB)


@partial(jax.jit, static_argnames=("n_buckets", "n2"))
def _stable_sort_a_payload(keys, dkeys, payloads, n_buckets, n2):
    """The form before: jax.lax.sort's default (is_stable=True: on the chip a
    third operand, an iota), ONE payload a sort, the key rebuilt for each."""
    T, n_pad = keys.shape

    def one(payload):
        ck = jnp.concatenate([keys, dkeys], axis=1) * np.int32(n2) + jnp.arange(n2, dtype=jnp.int32)
        if payload.ndim == 1:
            payload = jnp.broadcast_to(payload, (T, n_pad))
        full = jnp.concatenate([payload, jnp.zeros((T, n2 - n_pad), payload.dtype)], axis=1)
        return jax.lax.sort((ck, full), num_keys=1, dimension=1)[1]

    return tuple(one(p) for p in payloads)


def _layout(seed=3):
    """A layout with everything in it: buckets that repeat a key thousands of
    times, rows that stopped in the shallow phase, rows out of the bag (both
    stray), an empty bucket, and the filler each segment takes."""
    rng = np.random.default_rng(seed)
    rel = rng.integers(0, NB + 2, (T, N_PAD)).astype(np.int32)
    rel[rel == 5] = 4                                               # bucket 5 is empty
    w = rng.poisson(1.0, (T, N_PAD)).astype(np.float32)
    keys, dkeys, tile_seg, kept = forest_mxu._deep_layout(jnp.asarray(rel), jnp.asarray(w), n_buckets=NB, n2=N2)
    return keys, dkeys, np.asarray(tile_seg), np.asarray(kept)


def _payload(kind, seed):
    rng = np.random.default_rng(seed)
    shape = (N_PAD,) if kind.endswith("_1d") else (T, N_PAD)
    if kind.startswith("i32"):
        return jnp.asarray(rng.integers(-2**31, 2**31 - 1, shape, dtype=np.int64).astype(np.int32))
    return jnp.asarray((rng.standard_normal(shape) * 10.0 ** rng.integers(-3, 6, shape)).astype(np.float32))


GROUPS = {
    "i32": ("i32",), "i32_1d": ("i32_1d",), "f32": ("f32",), "f32_1d": ("f32_1d",),
    "three_words": ("i32", "i32", "i32"),
    "words_w_y": ("i32_1d", "i32_1d", "f32", "f32_1d"),
    "every_kind": ("i32", "f32_1d", "i32_1d", "f32", "i32"),
}


@pytest.mark.parametrize("group", list(GROUPS))
def test_sorted_payloads_are_the_stable_one_a_sort_forms_element_for_element(group):
    keys, dkeys, _seg, _kept = _layout()
    payloads = tuple(_payload(kind, 10 + i) for i, kind in enumerate(GROUPS[group]))
    got = forest_mxu._sort_part(keys, dkeys, payloads, n_buckets=NB, n2=N2)
    want = _stable_sort_a_payload(keys, dkeys, payloads, n_buckets=NB, n2=N2)
    assert len(got) == len(payloads)
    got, want = jax.device_get((got, want))
    for g, w, p in zip(got, want, payloads):
        assert g.shape == (T, N2) and g.dtype == p.dtype
        np.testing.assert_array_equal(g, w)


def test_the_combined_key_is_unique_over_every_trees_positions():
    """What is_stable=False rests on, on data: segment * n2 + column takes n2
    different values a tree, though a segment's own key repeats thousands of
    times, and it stays inside int32."""
    keys, dkeys, tile_seg, kept = _layout()
    every = np.concatenate([np.asarray(keys), np.asarray(dkeys)], axis=1).astype(np.int64)
    assert every.shape == (T, N2) and every.min() >= 0 and every.max() == NB
    assert max(np.bincount(row).max() for row in every) > 200          # the segment keys are far from unique
    ck = every * N2 + np.arange(N2)
    assert ck.max() < 2**31 and (NB + 1) * N2 < 2**31
    for row in ck:
        assert np.unique(row).size == N2
    # sorted by it, the rows lie segment by segment, as the tile map was counted
    tile = forest_mxu._ROW_TILE_DEEP
    for t in range(T):
        np.testing.assert_array_equal(np.sort(every[t])[::tile], tile_seg[t])
        assert (tile_seg[t][: kept[t]] < NB).all() and (tile_seg[t][kept[t]:] == NB).all()


@pytest.mark.parametrize("group", ["i32", "three_words", "words_w_y"])
def test_the_lowered_sort_is_unstable_and_carries_the_key_and_the_payloads_alone(group):
    keys, dkeys, _seg, _kept = _layout()
    payloads = tuple(_payload(kind, 20 + i) for i, kind in enumerate(GROUPS[group]))
    text = forest_mxu._sort_part.lower(keys, dkeys, payloads, n_buckets=NB, n2=N2).as_text()
    sorts = re.findall(r'"?stablehlo\.sort"?\(([^)]*)\)', text)
    assert len(sorts) == 1, text
    assert len(sorts[0].split(",")) == 1 + len(payloads)
    assert len(re.findall(r"is_stable = false", text)) == 1 and "is_stable = true" not in text
    # one compare decides: the key's
    comparator = text[text.index("stablehlo.sort"):]
    assert len(re.findall(r"stablehlo\.compare", comparator[: comparator.index("})")])) == 1
    # the form before, for the contrast: stable
    assert "is_stable = true" in _stable_sort_a_payload.lower(keys, dkeys, payloads, n_buckets=NB, n2=N2).as_text()


@pytest.mark.parametrize("n,sizes", [(9, [4, 5]), (16, [4, 4, 4, 4]), (4, [4]), (6, [3, 3]), (1, [1]), (252, [4] * 3 + [5] * 48)])
def test_the_groups_cover_every_payload_once_in_order_evenly_filled(n, sizes):
    """rf_higgs_fit's 9 payloads, rf_clf_fit's 16, a test's, and a deep regressor's
    at 1000 columns a split: the fewest sorts of at most five payloads."""
    groups = forest_mxu._sort_groups(n)
    assert [g0 for g0, _ in groups] == [0] + [g1 for _, g1 in groups[:-1]] and groups[-1][1] == n
    assert sorted(g1 - g0 for g0, g1 in groups) == sizes and max(sizes) <= forest_mxu._SORT_GROUP


def test_a_sorts_name_states_its_payloads():
    a = forest_mxu.aval
    i2, i1, f2, f1 = a((T, N_PAD), jnp.int32), a((N_PAD,), jnp.int32), a((T, N_PAD), jnp.float32), a((N_PAD,), jnp.float32)
    assert forest_mxu._sort_name((i2,)) == "sort_part_i32" and forest_mxu._sort_name((f1,)) == "sort_part_f32_1d"
    assert forest_mxu._sort_name((i2, i2, i2, i2)) == "sort_part_i32x4"
    assert forest_mxu._sort_name((i1, i1, f2, f1)) == "sort_part_i32_1dx2_f32_f32_1d"


def _grow(kind, cols=40, width=13, trees=2, depth=9, rows=2048, seed=7):
    rng = np.random.default_rng(3)
    bins = jnp.asarray(rng.integers(0, BINS, (cols, rows)), jnp.int8)
    w = jnp.asarray(rng.poisson(1.0, (trees, rows)).astype(np.float32))
    if kind == "regression":
        y = (25.0 + 180.0 * rng.standard_normal(rows)).astype(np.float32)
        stats3 = jnp.asarray(np.stack([np.ones_like(y), y, y * y]))
        base = stats3[:2]
    else:
        y = rng.integers(0, 2, rows).astype(np.float32)
        base, stats3 = jnp.asarray(np.stack([y == 0, y == 1]).astype(np.float32)), None
    (buf, kept), _plan = forest_mxu.grow_forest_mxu_device(
        forest_hist.tile_feature_rows(bins), base, w, stats3, max_depth=depth, n_bins=BINS, kind=kind, max_features=width,
        min_samples_leaf=1.0, min_impurity_decrease=0.0, seed=seed, y_vals=jnp.asarray(y), interpret=True,
    )
    return np.asarray(buf), np.asarray(kept)


@pytest.mark.parametrize("kind", ["gini", "regression"])
def test_a_deep_forest_is_the_stable_one_a_sort_forms_to_the_bit(kind, monkeypatch):
    """40 columns (wider than a feature block: shared subsets), depth 9: two deep
    levels of a classifier, three of a regressor, past the sort.  Grown with the
    form that ships and with the form before in _sort_part's place: the same
    tree_buf and the same kept tiles, bit for bit."""
    before = profiling.counters("forest.")
    buf, kept = _grow(kind)
    moved = profiling.counter_deltas(before, "forest.")
    assert moved["forest.sort_fits"] == moved["forest.unique_key_sort_fits"] == 1
    global_precompiler().clear()                                     # same keys, another function
    monkeypatch.setattr(forest_mxu, "_sort_part", _stable_sort_a_payload)
    buf_before, kept_before = _grow(kind)
    global_precompiler().clear()
    assert buf.view(np.uint32).tobytes() == buf_before.view(np.uint32).tobytes()
    np.testing.assert_array_equal(kept, kept_before)
    split = buf[forest_mxu._CH_OK] > 0.5
    first_deep = 2 ** (forest_mxu._shallow_levels(2) + 1) - 1            # the first node of the bucket level
    assert split[:, first_deep:].sum() > 20, "the deep phase split nodes"


def _mxu_everywhere(monkeypatch):
    monkeypatch.setattr(program, "_mxu_eligible", lambda *a, **k: True)


@pytest.mark.parametrize("estimator,depth,deep", [
    (RandomForestClassifier, 8, True), (RandomForestRegressor, 7, True), (RandomForestClassifier, 6, False), (RandomForestRegressor, 6, False),
], ids=["deep_classifier", "deep_regressor", "depth6_classifier", "depth6_regressor"])
def test_a_deep_fit_counts_its_sorts_and_a_depth_6_fit_counts_none(estimator, depth, deep, monkeypatch):
    """forest.sort_fits, forest.unique_key_sort_fits, forest.sort_dispatches and
    forest.sort_operands beside forest.fits, in the fit's telemetry and process-wide,
    static at dispatch: the payloads are ceil(F / 4) packed words, w and y; a sort
    carries its key and its group's payloads."""
    _mxu_everywhere(monkeypatch)
    rng = np.random.default_rng(5)
    X = rng.standard_normal((2048, 40)).astype(np.float32)
    y = X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.standard_normal(2048)
    labels = (y > 0).astype(np.float32) if estimator is RandomForestClassifier else y.astype(np.float32)
    before = profiling.counters("forest.")
    model = estimator(numTrees=2, maxDepth=depth, maxBins=BINS, seed=3, num_workers=1).fit(DataFrame.from_numpy(X, y=labels))
    width = int(np.sqrt(40)) if estimator is RandomForestClassifier else 40 // 3
    payloads = -(-width // 4) + 2
    groups = forest_mxu._sort_groups(payloads)
    want = {
        "forest.sort_fits": 1, "forest.unique_key_sort_fits": 1, "forest.sort_dispatches": len(groups),
        "forest.sort_operands": len(groups) + payloads,
    } if deep else dict.fromkeys(("forest.sort_fits", "forest.unique_key_sort_fits", "forest.sort_dispatches", "forest.sort_operands"), 0)
    for moved in (model.fit_telemetry().counters, profiling.counter_deltas(before, "forest.")):
        assert moved["forest.fits"] == 1
        assert {name: moved.get(name, 0) for name in want} == want
