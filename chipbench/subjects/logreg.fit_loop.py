"""LogisticRegression under fit_loop: the labelled table from the seed, the
estimator's public fit on the device-resident frame, and the comparison of every
job with references/logreg.py.  The limits are logreg.fit_loop.json's."""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from spark_rapids_ml_tpu import LogisticRegression
from spark_rapids_ml_tpu.dataframe import DataFrame

from chipbench import program
from chipbench.references import logreg as ref
from chipbench.references.common import local_shards


def stage(ctx) -> None:
    d = ctx.config["data"]
    ctx.mesh = program.get_mesh(ctx.chips)
    make = ref.table_fn(
        ctx.mesh, program.DATA_AXIS, d["rows_per_chip"], d["cols"], d["scale_decades"], d["flip_share"]
    )
    ctx.X, ctx.y = make(ctx.words)
    ctx.rows = d["rows_per_chip"] * ctx.chips
    ctx.jitted["table"] = (make, (ctx.words,))


def job(ctx):
    est = LogisticRegression(num_workers=ctx.chips, **ctx.config["estimator"]["params"])
    # from_device keeps labels on the host: 4 bytes a row, fetched once
    frame = DataFrame.from_device(ctx.X, y=np.asarray(ctx.y), n_rows=ctx.rows)

    def run() -> Dict[str, Any]:
        model = est.fit(frame)
        return {
            "iters": int(np.ravel(model.num_iters)[0]),
            "theta": np.concatenate([np.ravel(model.coef_), np.ravel(model.intercept_)]),
        }

    return run


def check(ctx, jobs: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    iters = ctx.config["expected_iters"]
    reg = ctx.config["estimator"]["params"]["regParam"]
    n_params = ctx.config["data"]["cols"] + 1
    f_exact = ref.objective(ctx.X, ctx.y, reg)
    theta_ref, ref_iters = ref.lbfgs(f_exact, n_params, iters)
    prec = ctx.reference_precision
    if prec != "highest":
        theta, it = ref.lbfgs(ref.objective(ctx.X, ctx.y, reg, prec), n_params, iters)
        jobs = [{"iters": it, "theta": theta}]
    sample = local_shards(ctx.X)[0][: ctx.check["score_rows"]]
    f_ref = f_exact(theta_ref)[0]
    score, coef, obj, wrong_iters = 0.0, 0.0, 0.0, 0
    seen: Dict[bytes, Any] = {}
    for j in jobs:
        key = np.asarray(j["theta"]).tobytes()
        if key not in seen:
            th = np.asarray(j["theta"], np.float64)
            seen[key] = (
                ref.score_gap(sample, th, theta_ref),
                float(np.linalg.norm(th[:-1] - theta_ref[:-1]) / np.linalg.norm(theta_ref[:-1])),
                abs(f_exact(th)[0] - f_ref) / f_ref,
            )
        s, c, o = seen[key]
        # np.maximum, not max: a NaN stays a NaN, and fails its limit
        score, coef, obj = (float(np.maximum(a, b)) for a, b in ((score, s), (coef, c), (obj, o)))
        j["ok"] = j["iters"] == iters
        wrong_iters += not j["ok"]
    ctx.detail = {"coef_gap": coef, "reference_iters": ref_iters, "distinct_results": len(seen)}
    return [
        {"name": "wrong_iters", "value": wrong_iters},
        {"name": "score_gap", "value": score},
        {"name": "objective_gap", "value": obj},
    ]
