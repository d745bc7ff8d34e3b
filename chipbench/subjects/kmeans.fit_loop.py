"""KMeans under fit_loop: the table from the seed, the estimator's public fit on
the device-resident frame, and the comparison of every job with
references/kmeans.py.  The limits are kmeans.fit_loop.json's."""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from spark_rapids_ml_tpu import KMeans
from spark_rapids_ml_tpu.dataframe import DataFrame

from chipbench import program
from chipbench.references import kmeans as ref

PERCENTILES = (25, 50, 75, 90, 95, 99, 100)


def stage(ctx) -> None:
    """The table, made on the device from the seed; rows_per_chip on each chip."""
    d = ctx.config["data"]
    ctx.mesh = program.get_mesh(ctx.chips)
    make = ref.table_fn(
        ctx.mesh, program.DATA_AXIS, d["rows_per_chip"], d["cols"], d["k_true"], d["ridges"], d["ridge_share"], d["ridge_scale"]
    )
    (ctx.X,) = make(ctx.words)
    ctx.rows = d["rows_per_chip"] * ctx.chips
    ctx.jitted["table"] = (make, (ctx.words,))


def job(ctx):
    """jit-free closure state: the estimator and the frame the window fits."""
    p = ctx.config["estimator"]["params"]
    est = KMeans(seed=int(ctx.seed) & 0x7FFFFFFF, num_workers=ctx.chips, **p)
    frame = DataFrame.from_device(ctx.X, n_rows=ctx.rows)

    def run() -> Dict[str, Any]:
        model = est.fit(frame)   # ends in the fetch of the model's arrays
        return {
            "iters": int(model.n_iter_),
            "centres": model.cluster_centers_,
            "inertia": float(model.inertia_),
        }

    return run


def check(ctx, jobs: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Every job of the window against one exact Lloyd update of its own centres
    (all rows, HIGHEST, in row blocks on the device), per centre, and its inertia_
    against the exact cost of those centres.  The lower quartile over centres reads
    the precision of the fit's sums (the centres that had stopped and that no
    near-tie touched); higher percentiles are held where a cell's limits hold
    them: against the lower precision where most centres have stopped, against
    gross faults in a part of the centres everywhere."""
    iters = ctx.config["expected_iters"]
    if ctx.reference_precision != "highest":
        # the control: the reference's own fit, in the lower precision, stands in
        # the program's place, from seeded rows of its own choosing
        k = ctx.config["estimator"]["params"]["k"]
        rows0 = np.asarray(ref.seeded_row_ids(ctx.words, ctx.rows, k))
        centres = ref.lloyd(ctx.X, np.asarray(ctx.X[rows0]), iters, ctx.reference_precision)
        jobs = [{"iters": iters, "centres": centres, "inertia": ref.inertia(ctx.X, centres)}]
    worst_of = {p: 0.0 for p in PERCENTILES}
    inertia_gap, wrong_iters = 0.0, 0
    seen: Dict[bytes, Any] = {}
    for j in jobs:
        key = np.asarray(j["centres"]).tobytes()
        if key not in seen:      # back-to-back fits of one frame repeat bit for bit
            gaps = ref.fixed_point_gaps(j["centres"], ref.lloyd(ctx.X, j["centres"], 1))
            cost = ref.inertia(ctx.X, j["centres"])
            seen[key] = (np.percentile(gaps, PERCENTILES), abs(j["inertia"] - cost) / cost)
        at, i = seen[key]
        for p, v in zip(PERCENTILES, at):
            worst_of[p] = float(np.maximum(worst_of[p], v))     # a NaN stays a NaN, and fails its limit
        inertia_gap = float(np.maximum(inertia_gap, i))
        j["ok"] = j["iters"] == iters
        wrong_iters += not j["ok"]
    ctx.detail = {"fixed_point_gap_percentiles": {str(p): v for p, v in worst_of.items()}, "distinct_results": len(seen)}
    return [
        {"name": "wrong_iters", "value": wrong_iters},
        {"name": "fixed_point_gap_quartile", "value": worst_of[25]},
        {"name": "fixed_point_gap_p75", "value": worst_of[75]},
        {"name": "fixed_point_gap_p90", "value": worst_of[90]},
        {"name": "fixed_point_gap_p95", "value": worst_of[95]},
        {"name": "fixed_point_gap_worst", "value": worst_of[100]},
        {"name": "inertia_gap", "value": inertia_gap},
    ]
