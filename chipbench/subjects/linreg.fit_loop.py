"""LinearRegression under fit_loop: the labelled table from the seed, the
estimator's public fit on the device-resident frame, and the comparison of every
distinct result of the window with references/linreg.py's residual-form
coordinate descent.  The limits are linreg.fit_loop.json's."""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu import LinearRegression
from spark_rapids_ml_tpu.dataframe import DataFrame

from chipbench import program
from chipbench.references import linreg as ref

NUMBERS = ("coef_gap", "small_coef_gap", "intercept_gap", "objective_gap")
PROGRAM_FAULTS = ("gram",)      # planted in the program's own fit; ref.FAULTS are planted in the reference


def stage(ctx) -> None:
    d = ctx.config["data"]
    ctx.mesh = program.get_mesh(ctx.chips)
    make = ref.table_fn(
        ctx.mesh, program.DATA_AXIS, d["rows_per_chip"], d["cols"], d["informative"], d["coef_scale"], d["noise"], d["bias"]
    )
    ctx.X, ctx.y = make(ctx.words)
    ctx.rows = d["rows_per_chip"] * ctx.chips
    ctx.jitted["table"] = (make, (ctx.words,))


def job(ctx):
    est = LinearRegression(num_workers=ctx.chips, **ctx.config["estimator"]["params"])
    # from_device keeps labels on the host: 4 bytes a row, fetched once
    frame = DataFrame.from_device(ctx.X, y=np.asarray(ctx.y), n_rows=ctx.rows)

    def run() -> Dict[str, Any]:
        model = est.fit(frame)       # ends in the one batched fetch of coefficients, means and sweeps
        return {"iters": int(model.num_iters), "coef": np.asarray(model.coef_, np.float64), "intercept": float(model.intercept_)}

    return run


def _fault(ctx) -> Optional[str]:
    """The planted fault a run names (`--control fault_<name>`), or None."""
    how = ctx.reference_precision
    return how[len("fault_"):] if how.startswith("fault_") else None


def _one_pass_gram(ctx) -> Dict[str, Any]:
    """fault_gram: the program's own public fit with its Gram pass at default
    precision, operands rounded to bfloat16 and one pass of the MXU (what a
    missing Precision.HIGHEST gives on the chip; the casts are spelled out so
    that a CPU shows it too)."""
    from spark_rapids_ml_tpu.ops import glm, linalg

    def one_pass(a, b):
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), preferred_element_type=jnp.float32)

    exact, linalg.exact_matmul = linalg.exact_matmul, one_pass
    glm.linreg_sufficient_stats.clear_cache()
    try:
        return ctx.job()
    finally:
        linalg.exact_matmul = exact
        glm.linreg_sufficient_stats.clear_cache()


def _stand_in(ctx, alpha: float, l1r: float, iters: int, fit_intercept: bool) -> Dict[str, Any]:
    """The control and the planted faults, in the program's place: the reference's
    own fit with every product's operands held in bfloat16 ("bf16") or with a
    fault planted in it ("fault_" + one of ref.FAULTS), or the program's fit on a
    one-pass Gram ("fault_gram")."""
    how, fault = ctx.reference_precision, _fault(ctx)
    if fault in PROGRAM_FAULTS:
        return _one_pass_gram(ctx)
    if how != "bf16" and fault not in ref.FAULTS:
        raise ValueError(f"control {how!r}: bf16, or fault_ + one of {ref.FAULTS + PROGRAM_FAULTS}")
    coef, intercept, ran = ref.cd(ctx.X, ctx.y, alpha, l1r, iters, fit_intercept, "bf16" if how == "bf16" else "highest", fault)
    return {"iters": ran, "coef": coef, "intercept": intercept}


def check(ctx, jobs: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """linreg.fit_loop.json's `what`."""
    p, d = ctx.config["estimator"]["params"], ctx.config["data"]
    iters, alpha, l1r, fit_intercept = ctx.config["expected_iters"], p["regParam"], p["elasticNetParam"], p["fitIntercept"]
    b_ref, b0_ref, _ = ref.cd(ctx.X, ctx.y, alpha, l1r, iters, fit_intercept)
    if ctx.reference_precision != "highest":
        jobs = [_stand_in(ctx, alpha, l1r, iters, fit_intercept)]
    f = ref.objective(ctx.X, ctx.y, alpha, l1r)
    f_ref = f(b_ref, b0_ref)
    small = slice(d["informative"], None)       # the columns whose true coefficient is 0
    small_rms = float(np.sqrt(np.mean(b_ref[small] ** 2)))
    y = np.asarray(ctx.y, np.float64)
    mean_se = float(y.std() / np.sqrt(y.size))      # the standard error of the labels' mean: an intercept's own scale
    worst = {k: 0.0 for k in NUMBERS}
    seen: Dict[bytes, Dict[str, float]] = {}
    wrong_iters = 0
    for j in jobs:
        j["ok"] = j["iters"] == iters
        wrong_iters += not j["ok"]
        b = np.asarray(j["coef"], np.float64)
        key = b.tobytes() + np.float64(j["intercept"]).tobytes()
        if key not in seen:      # back-to-back fits of one frame repeat bit for bit
            seen[key] = {
                "coef_gap": float(np.linalg.norm(b - b_ref) / np.linalg.norm(b_ref)),
                "small_coef_gap": float(np.abs(b[small] - b_ref[small]).max() / small_rms),
                "intercept_gap": abs(j["intercept"] - b0_ref) / mean_se,
                "objective_gap": abs(f(b, j["intercept"]) - f_ref) / f_ref,
            }
        for k in NUMBERS:        # np.maximum, not max: a NaN stays a NaN, and fails its limit
            worst[k] = float(np.maximum(worst[k], seen[key][k]))
    ctx.detail = {
        "distinct_results": len(seen), "objective": f_ref, "reference_intercept": b0_ref, "small_rms": small_rms,
        "nonzero": int(np.count_nonzero(b_ref)), "sweeps": sorted({j["iters"] for j in jobs}),
    }
    return [{"name": "wrong_iters", "value": wrong_iters}] + [{"name": k, "value": worst[k]} for k in NUMBERS]
