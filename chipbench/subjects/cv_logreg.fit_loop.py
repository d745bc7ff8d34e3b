"""CrossValidator over LogisticRegression under fit_loop: the labelled table from
the seed (logreg-d3000-iter200's), the validator's public fit on the
device-resident frame, and the comparison of every distinct job of the window with
references/cv_logreg.py.  The limits are cv_logreg.fit_loop.json's."""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from spark_rapids_ml_tpu import LogisticRegression
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.evaluation import MulticlassClassificationEvaluator
# scoring where the table lies: a program without it cannot score a from_device
# frame at all, and ends the cell here, at import, at once
from spark_rapids_ml_tpu.ops.logistic import sweep_logistic_score_kernel  # noqa: F401
from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder

from chipbench import program
from chipbench.references import cv_logreg as ref
from chipbench.references.common import local_shards

NUMBERS = ("metric_gap", "score_gap", "objective_gap", "best_score_gap", "best_objective_gap")
LOWER = ("bf16", "fp8")     # the controls: the reference's own arithmetic in a lower precision
FAULTS = ("leak",)          # fault_leak: a sub-model trained WITH its validation rows


def stage(ctx) -> None:
    d = ctx.config["data"]
    ctx.mesh = program.get_mesh(ctx.chips)
    make = ref.table_fn(
        ctx.mesh, program.DATA_AXIS, d["rows_per_chip"], d["cols"], d["scale_decades"], d["flip_share"]
    )
    ctx.X, ctx.y = make(ctx.words)
    ctx.rows = d["rows_per_chip"] * ctx.chips
    ctx.fold_seed = int(ctx.seed) & 0x7FFFFFFF
    ctx.jitted["table"] = (make, (ctx.words,))


def _theta(model) -> np.ndarray:
    return np.concatenate([np.ravel(model.coef_), np.ravel(model.intercept_)])


def job(ctx):
    v = ctx.config["validator"]
    grid_values = v["grid"]["regParam"]
    est = LogisticRegression(num_workers=ctx.chips, **ctx.config["estimator"]["params"])
    grid = ParamGridBuilder().addGrid(est.getParam("regParam"), grid_values).build()
    cv = CrossValidator(
        estimator=est, estimatorParamMaps=grid,
        evaluator=MulticlassClassificationEvaluator(metricName=v["evaluator"]["metricName"]),
        numFolds=v["numFolds"], seed=ctx.fold_seed, parallelism=v["parallelism"], collectSubModels=v["collectSubModels"],
    )
    # from_device keeps labels on the host: 4 bytes a row, fetched once
    frame = DataFrame.from_device(ctx.X, y=np.asarray(ctx.y), n_rows=ctx.rows)

    def run() -> Dict[str, Any]:
        before = program.counters()
        model = cv.fit(frame)        # sweep, 24 held-out scores, the best refitted on all rows
        after = program.counters()
        subs = model.subModels
        return {
            "avg": np.asarray(model.avgMetrics, np.float64),
            "std": np.asarray(model.stdMetrics, np.float64),
            "best": grid_values.index(model.bestModel.getOrDefault("regParam")),
            "best_theta": _theta(model.bestModel),
            "sub_theta": np.stack([[_theta(m) for m in fold] for fold in subs]),
            "iters": [int(np.ravel(m.num_iters)[0]) for fold in subs for m in fold] + [int(np.ravel(model.bestModel.num_iters)[0])],
            # what the job moved of the program's counters: rows scored on the device,
            # bytes of rows that went up for scoring, tables staged
            "moved": {k: after.get(k, 0) - before.get(k, 0) for k in ("tuning.score.rows", "tuning.score.h2d_bytes", "ingest.staged")},
        }

    return run


def _fault(ctx) -> Optional[str]:
    how = ctx.reference_precision
    return how[len("fault_"):] if how.startswith("fault_") else None


def _pairs(ctx, swept: np.ndarray, iters: int, a_fold: int = 2) -> List[Tuple[int, int]]:
    """`a_fold` (fold, candidate) pairs a fold, drawn from the seed among the fold's
    lanes that stopped before `iters` (all of its lanes where too few did): a lane whose
    float32 objective has stopped changing is a settled model, and two sound fits of one
    agree closely; a lane cut off at `iters` is wherever its path had got to."""
    rng = np.random.default_rng(ctx.fold_seed)
    pairs = []
    for f, ran in enumerate(swept):
        settled = np.flatnonzero(ran < iters)
        among = settled if len(settled) >= a_fold else np.arange(len(ran))
        pairs += [(f, int(i)) for i in sorted(rng.choice(among, size=min(a_fold, len(among)), replace=False))]
    return pairs


def _gaps(ctx, sample, theta, theta_ref, w, reg) -> Tuple[float, float, float]:
    """(decision scores on the sample, the exact weighted objective, coefficients): a
    model against the reference's own fit of the same rows and penalty, all relative."""
    theta = np.asarray(theta, np.float64)
    f_exact = ref.objective(ctx.X, ctx.y, w, reg)
    f_ref = f_exact(theta_ref)[0]
    return (
        ref.score_gap(sample, theta, theta_ref),
        abs(f_exact(theta)[0] - f_ref) / f_ref,
        float(np.linalg.norm(theta[:-1] - theta_ref[:-1]) / np.linalg.norm(theta_ref[:-1])),
    )


def check(ctx, jobs: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """cv_logreg.fit_loop.json's `what`."""
    v, iters = ctx.config["validator"], ctx.config["expected_iters"]
    regs, n_folds = v["grid"]["regParam"], v["numFolds"]
    how, fault = ctx.reference_precision, _fault(ctx)
    if how != "highest" and how not in LOWER and fault not in FAULTS:
        raise ValueError(f"control {how!r}: one of {LOWER}, or fault_ + one of {FAULTS}")
    lower = how if how in LOWER else "highest"

    folds = ref.fold_ids(ctx.rows, n_folds, ctx.fold_seed)
    held_out = [(folds == f).astype(np.float32) for f in range(n_folds)]
    everyone = np.ones(ctx.rows, np.float32)
    sample = local_shards(ctx.X)[0][: ctx.check["score_rows"]]
    if getattr(ctx, "reference_fits", None) is None:     # a control's second comparison has the same table
        ctx.reference_fits = {}
    fits = ctx.reference_fits

    def reference_fit(fold: Optional[int], i: int):
        if (fold, i) not in fits:
            w = everyone if fold is None else ref.train_weights(folds, fold)
            fits[(fold, i)] = ref.fit(ctx.X, ctx.y, w, regs[i], iters)
        return fits[(fold, i)]

    worst = {k: 0.0 for k in NUMBERS}
    wrong_iters = best_wrong = moved_rows = 0
    seen: Dict[bytes, Dict[str, Any]] = {}
    for j in jobs:
        key = np.asarray(j["sub_theta"]).tobytes() + np.asarray(j["best_theta"]).tobytes() + np.asarray(j["avg"]).tobytes()
        if key not in seen:      # back-to-back sweeps of one frame repeat bit for bit
            sub, avg, std, best_theta = j["sub_theta"], j["avg"], j["std"], j["best_theta"]
            pairs = _pairs(ctx, np.asarray(j["iters"][:-1]).reshape(n_folds, len(regs)), iters)
            if how != "highest":
                # the control and the fault, in the program's place: the reference's own fits of
                # the sampled pairs in the lower precision (or with the fold's rows left in), and
                # the metrics the program would have reported from a scoring product that low
                sub = np.array(sub)
                for f, i in pairs:
                    w = everyone if fault == "leak" else ref.train_weights(folds, f)
                    sub[f, i] = ref.fit(ctx.X, ctx.y, w, regs[i], iters, lower)[0]
                said = np.array([[ref.held_out_log_loss(ctx.X, ctx.y, held_out[f], sub[f, i], lower) for i in range(len(regs))] for f in range(n_folds)])
                avg, std, _ = ref.select(said)
                if lower != "highest":
                    best_theta = ref.fit(ctx.X, ctx.y, everyone, regs[j["best"]], iters, lower)[0]
            # all 24 held-out metrics, of the coefficients the job returned, on the reference's own folds
            held = np.array([[ref.held_out_log_loss(ctx.X, ctx.y, held_out[f], sub[f, i]) for i in range(len(regs))] for f in range(n_folds)])
            ref_avg, ref_std, _ = ref.select(held)
            gaps = {k: 0.0 for k in NUMBERS}
            gaps["metric_gap"] = float(np.max(np.maximum(np.abs(avg - ref_avg), np.abs(std - ref_std)) / ref_avg))
            # the job's choice against the reference's: it must be a least mean, to the metric's rounding
            chosen_off = float((ref_avg[j["best"]] - ref_avg.min()) / ref_avg.min())
            # the sampled sub-models and the best model against the reference's own fits
            per_model = [_gaps(ctx, sample, sub[f, i], reference_fit(f, i)[0], ref.train_weights(folds, f), regs[i]) for f, i in pairs]
            # the MEDIAN over the sampled sub-models whose reference fit settled too (all of
            # them where none did): a lower precision moves every one of them, where a sound
            # lane whose float32 objective happened to stop changing a dozen iterations sooner
            # than the reference's, or stopped unconverged, moves one
            settled = [m for m, (f, i) in zip(per_model, pairs) if reference_fit(f, i)[1] < iters] or per_model
            gaps["score_gap"], gaps["objective_gap"] = (float(np.median([m[k] for m in settled])) for k in (0, 1))
            per_model.append(_gaps(ctx, sample, best_theta, reference_fit(None, j["best"])[0], everyone, regs[j["best"]]))
            gaps["best_score_gap"], gaps["best_objective_gap"] = per_model[-1][:2]
            seen[key] = {
                "gaps": gaps, "chosen_off": chosen_off, "pairs": pairs, "per_model": per_model, "ref_avg": ref_avg.tolist(), "avg": np.asarray(avg).tolist(),
                "reference_iters": [int(reference_fit(f, i)[1]) for f, i in pairs] + [int(reference_fit(None, j["best"])[1])],
            }
        got = seen[key]
        for k in NUMBERS:        # np.maximum, not max: a NaN stays a NaN, and fails its limit
            worst[k] = float(np.maximum(worst[k], got["gaps"][k]))
        # the sweep's shared loop made its expected_iters trips: its slowest lane ran them all
        # and none ran more; nor did the refit (a lane, or the refit, may stop sooner where its
        # float32 objective stops changing, as the reference's own fit does)
        right = max(j["iters"][:-1]) == iters and j["iters"][-1] <= iters
        chose = got["chosen_off"] <= ctx.limits["metric_gap"]
        on_device = j["moved"] == {"tuning.score.rows": ctx.rows, "tuning.score.h2d_bytes": 0, "ingest.staged": 0}
        if how == "highest":     # a control's comparison leaves the window's tally as it was
            j["ok"] = right and chose and on_device
        wrong_iters += not right
        best_wrong += not chose
        moved_rows += not on_device
    first = next(iter(seen.values()))
    ctx.detail = {
        "distinct_results": len(seen), "pairs": first["pairs"], "per_model": first["per_model"], "iters": jobs[0]["iters"],
        "reference_iters": first["reference_iters"], "avg": first["avg"], "reference_avg": first["ref_avg"], "best": jobs[0]["best"],
        "chosen_off": first["chosen_off"], "moved": jobs[0]["moved"],
    }
    return [
        {"name": "wrong_iters", "value": wrong_iters},
        {"name": "best_index_wrong", "value": best_wrong},
        {"name": "rows_moved", "value": moved_rows},
    ] + [{"name": k, "value": worst[k]} for k in NUMBERS]
