"""RandomForestClassifier under fit_loop: the labelled table from the seed, the
estimator's public fit on the device-resident frame, and the comparison of every
distinct forest of the window with references/forest.py: one exact split search
of its own nodes.  The limits are forest.fit_loop.json's."""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu import RandomForestClassifier
from spark_rapids_ml_tpu.dataframe import DataFrame

from chipbench import program
from chipbench.references import forest as ref

ARRAYS = ("features_", "thresholds_", "leaf_values_", "node_counts_", "impurities_")


def stage(ctx) -> None:
    d = ctx.config["data"]
    ctx.mesh = program.get_mesh(ctx.chips)
    make = ref.table_fn(
        ctx.mesh, program.DATA_AXIS, d["rows_per_chip"], d["cols"], d["classes"], d["informative"], d["redundant"], d["class_sep"]
    )
    ctx.X, ctx.y = make(ctx.words)
    ctx.rows = d["rows_per_chip"] * ctx.chips
    ctx.jitted["table"] = (make, (ctx.words,))


def job(ctx):
    est = RandomForestClassifier(seed=int(ctx.seed) & 0x7FFFFFFF, num_workers=ctx.chips, **ctx.config["estimator"]["params"])
    # from_device keeps labels on the host, as the estimator takes them
    frame = DataFrame.from_device(ctx.X, y=np.asarray(ctx.y), n_rows=ctx.rows)

    def run() -> Dict[str, Any]:
        model = est.fit(frame)       # ends in the one batched fetch of the forest
        out = {a: getattr(model, a) for a in ARRAYS}
        out["bootstrap_draw_"] = model.bootstrap_draw_
        return out

    return run


def _max_features(ctx) -> int:
    """featureSubsetStrategy as the estimator resolves it for a classifier of
    several trees: auto and sqrt are int(sqrt(cols)); all is every column."""
    how = ctx.config["estimator"]["params"].get("featureSubsetStrategy", "auto")
    cols = ctx.config["data"]["cols"]
    return cols if how == "all" else max(1, int(np.sqrt(cols)))


def _shallowest(features: np.ndarray) -> int:
    """The depth of the forest's shallowest tree: the deepest level that holds a node."""
    deepest = [int(np.floor(np.log2(np.flatnonzero(f >= 0).max() + 1))) + 1 if (f >= 0).any() else 0 for f in features]
    return min(deepest)


def _exact(j: Dict[str, Any], trees: int, max_depth: int) -> bool:
    """A job's own count: exactly numTrees trees of the dense layout's node
    count (so depth <= maxDepth), and no NaN anywhere."""
    shape = (trees, 2 ** (max_depth + 1) - 1)
    return all(np.asarray(j[a]).shape[:2] == shape for a in ARRAYS) and not any(np.isnan(np.asarray(j[a], np.float64)).any() for a in ARRAYS)


def _fault(ctx) -> Optional[str]:
    """The planted fault a run names (`--control fault_<name>`), or None."""
    how = ctx.reference_precision
    return how[len("fault_"):] if how.startswith("fault_") else None


def _weights(ctx, draw, trees=None) -> np.ndarray:
    """(trees, rows) bootstrap counts drawn again from a forest's bootstrap_draw_
    by the program's one rule (only the trees asked for cross the link); a
    planted draw_* fault hands over a degenerate draw in its place, to builder
    and check alike."""
    w, fault = ref.bootstrap_weights(draw), _fault(ctx)
    pick = slice(None) if trees is None else np.asarray(trees)
    if fault in ref.DRAW_FAULTS:
        return ref.faulty_draw(np.asarray(w), fault)[pick, :ctx.rows]
    return np.asarray(w[pick])[:, :ctx.rows]


def _stand_in(ctx, edges, y) -> Dict[str, Any]:
    """The control and the planted faults: the reference's own whole trees stand in
    the program's place, built with histogram sums held in bfloat16 ("bf16"), with
    a fault planted in the builder ("fault_" + one of ref.FAULTS), from a
    degenerate draw ("fault_" + one of ref.DRAW_FAULTS), or binned and split by
    edges held in bfloat16 ("fault_edges")."""
    p, c = ctx.config["estimator"]["params"], ctx.check
    how, fault = ctx.reference_precision, _fault(ctx)
    if how != "bf16" and fault not in ref.FAULTS + ref.DRAW_FAULTS + ("edges",):
        raise ValueError(f"control {how!r}: bf16, or fault_ + one of {ref.FAULTS + ref.DRAW_FAULTS + ('edges',)}")
    trees = c["control_trees"]
    draw = np.array([int(ctx.seed) & 0x7FFFFFFF, trees, ctx.rows, 0], np.int64)
    if fault == "edges":
        edges = ref.in_bf16(edges)
    out = ref.build_forest(
        ref.columns_fn(ctx.X, edges), edges, y, _weights(ctx, draw), ctx.config["data"]["classes"], p["maxDepth"],
        _max_features(ctx), int(ctx.seed), "bf16" if how == "bf16" else "float64", fault if fault in ref.FAULTS else None,
    )
    return dict(out, bootstrap_draw_=draw)


def _examine(ctx, j: Dict[str, Any], edges: np.ndarray, y: np.ndarray, rng: np.random.Generator) -> Dict[str, float]:
    """One forest against one exact split search of its own nodes (steps 1 to 4
    of forest.fit_loop.json's `what`)."""
    d, p, c = ctx.config["data"], ctx.config["estimator"]["params"], ctx.check
    classes, depth, n_bins, n = d["classes"], p["maxDepth"], p["maxBins"], ctx.rows
    feats_, thr_ = np.asarray(j["features_"]), np.asarray(j["thresholds_"])
    T = feats_.shape[0]
    took: Dict[str, float] = {}
    t0 = time.perf_counter()

    # 1. routing and counts: every node of a seeded sample of trees
    trees = np.sort(rng.choice(T, min(c["trees"], T), replace=False))
    weights = _weights(ctx, j["bootstrap_draw_"], trees).astype(np.float64)
    draws = ref.poisson_like(weights)
    paths = np.asarray(ref.walk_fn(depth)(ctx.X, jnp.asarray(feats_[trees]), jnp.asarray(thr_[trees])))
    count_mismatch, impurity_gap = 0, 0.0
    for i, t in enumerate(trees):
        exact = ref.node_counts(paths[:, i], y, weights[i], classes, depth)
        told_w = np.asarray(j["node_counts_"][t], np.float64)
        told = np.asarray(j["leaf_values_"][t], np.float64) * told_w[:, None]
        count_mismatch += int((np.abs(told - exact) > 0.5).sum() + (np.abs(told_w - exact.sum(-1)) > 0.5).sum())
        held = exact.sum(-1) > 0
        impurity_gap = max(impurity_gap, float(np.abs(np.asarray(j["impurities_"][t], np.float64) - ref.gini(exact))[held].max()))
    took["route_s"] = time.perf_counter() - t0

    # 2. and 3. split optimality and the subset's width: a seeded sample of
    # internal nodes of those trees, the deep levels weighted up
    t1 = time.perf_counter()
    per_level = [
        c["nodes_shallow"] if level < c["deep_from"] else c["nodes_deep"] if level <= c["duel_to"] else c["nodes_below"]
        for level in range(depth)
    ]
    noise0 = d["informative"] + d["redundant"]
    width = min(_max_features(ctx), d["cols"] - noise0)
    take = ref.columns_fn(ctx.X, edges)
    shortfall, implied_gap, threshold_gap, wins, duels, searched = 0.0, 0.0, 0.0, 0.0, 0, 0
    for i, t in enumerate(trees):
        picks = []
        for level in range(depth):
            ids = np.arange(2**level - 1, 2 ** (level + 1) - 1)
            ids = ids[feats_[t, ids] >= 0]
            picks += list(rng.choice(ids, min(per_level[level], ids.size), replace=False))
        if not picks:
            continue
        picks = np.asarray(picks)
        level_of = np.floor(np.log2(picks + 1)).astype(int)
        # each node's own feature (one gather of a fixed width), and `width` noise
        # columns of the reference's own choosing, one draw a tree
        own_bins = take(np.resize(feats_[t, picks], sum(per_level)))
        rival_bins = take(noise0 + rng.choice(d["cols"] - noise0, width, replace=False))
        for k, (nd, lv) in enumerate(zip(picks, level_of)):
            rows = np.flatnonzero(paths[lv, i] == nd)
            yr, wr = y[rows], weights[i][rows]
            gains, weighted = ref.split_search(own_bins[rows, k:k + 1], yr, wr, classes, n_bins)
            e = np.abs(edges[feats_[t, nd]] - thr_[t, nd])
            at = int(e.argmin())
            threshold_gap = max(threshold_gap, float(e[at] / max(abs(float(thr_[t, nd])), 1e-6)))
            scale = max(weighted, 1e-300)
            # a stored split the reference finds inadmissible (no weight on a side) reads inf
            shortfall = max(shortfall, float((gains[0].max() - gains[0, at]) / scale) if np.isfinite(gains[0, at]) else float("inf"))
            told = float(j["impurities_"][t, nd]) * float(j["node_counts_"][t, nd]) - sum(
                float(j["impurities_"][t, kid]) * float(j["node_counts_"][t, kid]) for kid in (2 * nd + 1, 2 * nd + 2)
            )
            implied_gap = max(implied_gap, abs(told - float(gains[0, at])) / scale if np.isfinite(gains[0, at]) else float("inf"))
            searched += 1
            # the duel: a deep node that split on a noise column, against the best
            # of the reference's noise columns on the same rows
            if c["deep_from"] <= lv <= c["duel_to"] and feats_[t, nd] >= noise0:
                rival = ref.split_search(rival_bins[rows], yr, wr, classes, n_bins)[0].max()
                duels += 1
                wins += 1.0 if gains[0, at] > rival else 0.5 if gains[0, at] == rival else 0.0
    took["search_s"] = time.perf_counter() - t1

    # 4. the forest as a classifier, on rows the fit never saw
    t2 = time.perf_counter()
    Xf, yf = ref.fresh_fn(c["fresh_rows"], d["cols"], classes, d["informative"], d["redundant"], d["class_sep"])(ctx.words)
    leaves = ref.walk_host(np.asarray(Xf), feats_, thr_, depth)
    accuracy = float((ref.predict(leaves, np.asarray(j["leaf_values_"])) == np.asarray(yf)).mean())
    took["fresh_s"] = time.perf_counter() - t2
    win_share = wins / duels if duels else 0.0
    return {
        "count_mismatch": count_mismatch, "impurity_gap": impurity_gap, "threshold_gap": threshold_gap,
        "split_gain_shortfall": shortfall, "implied_gain_gap": implied_gap,
        "win_share_low": 0.5 - win_share, "win_share_high": win_share - 0.5, "accuracy_loss": 1.0 - accuracy,
        "draw_mean_gap": draws["mean_gap"], "draw_var_gap": draws["var_gap"], "draw_cross_corr": draws["cross_corr"],
        "draw_not_counts": draws["not_counts"],
        "detail": {"nodes_searched": searched, "duels": duels, "win_share": win_share, "accuracy": accuracy,
                   "nodes": int(2 * (feats_ >= 0).sum() + T), "shallowest_tree": _shallowest(feats_), "took_s": took},
    }


NUMBERS = (
    "count_mismatch", "impurity_gap", "threshold_gap", "split_gain_shortfall", "implied_gain_gap", "win_share_low",
    "win_share_high", "accuracy_loss", "draw_mean_gap", "draw_var_gap", "draw_cross_corr", "draw_not_counts",
)


def check(ctx, jobs: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    p = ctx.config["estimator"]["params"]
    y = np.asarray(ctx.y)
    sample = np.asarray(ctx.X[jnp.asarray(ref.sample_rows(ctx.rows, ctx.config["data"]["cols"]))])
    edges = ref.quantile_edges(sample, p["maxBins"])
    trees = p["numTrees"]
    if ctx.reference_precision != "highest":
        jobs = [_stand_in(ctx, edges, y)]
        trees = ctx.check["control_trees"]
    worst = {k: 0.0 for k in NUMBERS}
    seen: Dict[bytes, Dict[str, float]] = {}
    broken = 0
    for j in jobs:
        j["ok"] = _exact(j, trees, p["maxDepth"])
        broken += not j["ok"]
        if not j["ok"]:
            continue
        key = b"".join(np.asarray(j[a]).tobytes() for a in ("features_", "thresholds_", "node_counts_"))
        if key not in seen:      # back-to-back fits of one frame repeat bit for bit
            seen[key] = _examine(ctx, j, edges, y, np.random.default_rng(int(ctx.seed)))
        for k in NUMBERS:        # np.maximum, not max: a NaN stays a NaN, and fails its limit
            worst[k] = float(np.maximum(worst[k], seen[key][k]))
    ctx.detail = {"distinct_results": len(seen), **(next(iter(seen.values()))["detail"] if seen else {})}
    return [{"name": "broken_jobs", "value": broken}] + [{"name": k, "value": worst[k]} for k in NUMBERS]
