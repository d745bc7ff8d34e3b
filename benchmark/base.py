#
# BenchmarkBase: CLI parsing + timed execution + report records (reference
# python/benchmark/benchmark/base.py:32-283).  Differences are TPU-shaped, not
# structural: datasets load from parquet into the facade DataFrame (one
# partition per file, the role Spark partitions play in the reference), the
# class under test runs in-process on the device mesh, and `--mode cpu` swaps
# in a sklearn baseline the way the reference's CPU cluster runs swap in
# pyspark.ml classes (base.py:110-130 _class_params routing).
#

from __future__ import annotations

import argparse
import glob
import os
import pprint
from abc import abstractmethod
from datetime import datetime
from typing import Any, Dict, List, Optional, Tuple, Union

import numpy as np
import pandas as pd

from spark_rapids_ml_tpu.dataframe import DataFrame

from .utils import append_report, to_bool, with_benchmark


class BenchmarkBase:
    """Base class for per-algorithm benchmarks."""

    def __init__(self, argv: List[str]) -> None:
        print("=" * 100)
        print(self.__class__.__name__)
        self._parser = argparse.ArgumentParser(description=type(self).__name__)
        self._parser.add_argument(
            "--num_devices",
            type=int,
            default=0,
            help="devices in the mesh (0 = all local devices); the analog of "
            "the reference's --num_gpus (base.py:50-56)",
        )
        self._parser.add_argument("--num_runs", type=int, default=1)
        self._parser.add_argument("--report_path", type=str, default="")
        self._parser.add_argument(
            "--train_path", action="append", default=[], required=True
        )
        self._parser.add_argument("--transform_path", action="append", default=[])
        self._parser.add_argument(
            "--mode",
            type=str,
            default="tpu",
            choices=["tpu", "cpu"],
            help="tpu = this framework on the jax device mesh; cpu = sklearn "
            "baseline (the reference's Spark-CPU comparison arm)",
        )
        self._parser.add_argument(
            "--feature_type",
            type=str,
            default="multi_cols",
            choices=["multi_cols", "array"],
            help="pass features as D scalar columns or one array column "
            "(the reference tests' layout parametrization)",
        )
        self._add_class_arguments()
        self._add_extra_arguments()
        self._args = self._parser.parse_args(argv)
        self._class_params = {
            k: v
            for k, v in vars(self._args).items()
            if k in self._supported_class_params() and v is not None
        }
        print("class params:")
        pprint.pprint(self._class_params)

    # -- argument plumbing --------------------------------------------------
    def _add_extra_arguments(self) -> None:
        pass

    def _supported_class_params(self) -> Dict[str, Any]:
        """{param name: default or (default, help)} auto-turned into CLI args
        (reference base.py:103-130)."""
        return {}

    def _add_class_arguments(self) -> None:
        for name, value in self._supported_class_params().items():
            value, help_str = value if isinstance(value, tuple) else (value, None)
            help_str = help_str or "algorithm parameter"
            if value is None:
                raise RuntimeError(f"param {name}: convert None default to a type")
            if type(value) is type:
                self._parser.add_argument(f"--{name}", type=value, help=help_str)
            elif isinstance(value, bool):
                self._parser.add_argument(
                    f"--{name}", type=to_bool, default=value, help=help_str
                )
            else:
                self._parser.add_argument(
                    f"--{name}", type=type(value), default=value, help=help_str
                )

    @property
    def args(self) -> argparse.Namespace:
        return self._args

    # -- data loading -------------------------------------------------------
    def _expand_paths(self, paths: List[str]) -> List[str]:
        files: List[str] = []
        for p in paths:
            if os.path.isdir(p):
                found = sorted(glob.glob(os.path.join(p, "*.parquet"))) or sorted(
                    glob.glob(os.path.join(p, "*.csv"))
                )
                files.extend(found)
            else:
                files.extend(sorted(glob.glob(p)))
        if not files:
            raise FileNotFoundError(f"No parquet/csv files under {paths}")
        return files

    @staticmethod
    def _read_file(path: str) -> pd.DataFrame:
        if path.endswith(".csv"):
            # header line = column names; numeric payload loads through the
            # native threaded CSV reader (numpy fallback inside native.load_csv),
            # which row-counts natively — no Python pass over the file
            from spark_rapids_ml_tpu import native

            with open(path) as f:
                header = f.readline().strip().split(",")
            data = native.load_csv(path, None, len(header), skip_rows=1)
            return pd.DataFrame(data, columns=header)
        return pd.read_parquet(path)

    def load_dataframe(self, paths: List[str]) -> Tuple[DataFrame, Union[str, List[str]], Optional[str]]:
        """Parquet files -> facade DataFrame (one partition per file, like one
        Spark partition per file in the reference's 50-file datasets), plus
        (features_col, label_col)."""
        parts = [self._read_file(f) for f in self._expand_paths(paths)]
        cols = list(parts[0].columns)
        label_col = "label" if "label" in cols else None
        feature_cols = [c for c in cols if c != label_col]
        features_col: Union[str, List[str]]
        if self._args.feature_type == "array":
            packed = []
            for p in parts:
                feats = np.ascontiguousarray(p[feature_cols].to_numpy())
                pdf = pd.DataFrame({"features": list(feats)})
                if label_col:
                    pdf[label_col] = p[label_col].to_numpy()
                packed.append(pdf)
            parts = packed
            features_col = "features"
        else:
            features_col = feature_cols
        return DataFrame(parts), features_col, label_col

    # -- execution ----------------------------------------------------------
    @staticmethod
    def _aggregate_runs(runs: List[Dict[str, Any]]) -> Dict[str, Any]:
        """Mean AND median per numeric metric over a multi-run session —
        single runs have been observed far apart (the kNN arm's 31.4%
        spread, BENCH_r05), so a mean alone can be dragged by one outlier;
        the median is the robust headline."""
        import statistics

        # only the measured metrics: timings and scores (class params and
        # run config are constants — averaging them is noise)
        keys = [
            k
            for k, v in runs[0].items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
            and (k.endswith("_time") or k in ("benchmark_time", "score"))
        ]
        agg: Dict[str, Any] = {"summary": True, "num_runs": len(runs)}
        for k in keys:
            vals = [
                float(r[k])
                for r in runs
                if isinstance(r.get(k), (int, float))
                and not isinstance(r.get(k), bool)
            ]
            if vals:
                agg[f"{k}_mean"] = round(statistics.fmean(vals), 6)
                agg[f"{k}_median"] = round(statistics.median(vals), 6)
        # per-phase spread attribution over the session (srml-scope): when
        # the runs carry phase_times (or per-repeat lists), report each
        # phase's max−min as % of the median timed call and name the top
        # contributor — the data behind the standings ⚠ footnote
        phase_runs = []
        for r in runs:
            per = r.get("phase_times_per_repeat")
            if isinstance(per, list):
                phase_runs.extend(p for p in per if isinstance(p, dict))
            elif isinstance(r.get("phase_times"), dict):
                phase_runs.append(r["phase_times"])
        base_key = (
            "transform_time"
            if "transform_time_median" in agg
            else "benchmark_time"
        )
        from spark_rapids_ml_tpu import profiling

        spread = profiling.spread_attribution(
            phase_runs, agg.get(f"{base_key}_median", 0.0)
        )
        if spread:
            agg["spread_attribution"] = spread
            agg["spread_phase"] = next(iter(spread))
        return agg

    def run(self) -> None:
        train_df, features_col, label_col = self.load_dataframe(self._args.train_path)
        transform_df = None
        if self._args.transform_path:
            transform_df, _, _ = self.load_dataframe(self._args.transform_path)
        all_runs: List[Dict[str, Any]] = []
        for run_idx in range(self._args.num_runs):
            results, benchmark_time = with_benchmark(
                f"benchmark run {run_idx}",
                lambda: self.run_once(train_df, features_col, transform_df, label_col),
            )
            results["benchmark_time"] = benchmark_time
            results["datetime"] = datetime.now().isoformat()
            results["run_idx"] = run_idx
            results["mode"] = self._args.mode
            results["num_devices"] = self._args.num_devices
            if self._args.mode == "tpu":
                # srml-scope export rides every artifact record: counters,
                # duration percentiles, and this thread's phase stats in the
                # stable JSON schema (docs/observability.md)
                from spark_rapids_ml_tpu import profiling

                results["metrics_export"] = profiling.export_metrics()
            results.update(self._class_params)
            print("-" * 100)
            pprint.pprint(results)
            append_report(self._args.report_path, results)
            all_runs.append(results)
        if len(all_runs) > 1:
            summary = self._aggregate_runs(all_runs)
            summary["datetime"] = datetime.now().isoformat()
            summary["mode"] = self._args.mode
            print("-" * 100)
            print("summary over runs (mean | median):")
            pprint.pprint(summary)
            append_report(self._args.report_path, summary)

    @abstractmethod
    def run_once(
        self,
        train_df: DataFrame,
        features_col: Union[str, List[str]],
        transform_df: Optional[DataFrame],
        label_col: Optional[str],
    ) -> Dict[str, Any]:
        """Fit (and transform if transform_df given), returning a metrics dict
        with at least fit_time / transform_time / total_time / score
        (reference base.py:272-283 + per-algo run_once)."""
        raise NotImplementedError

    # -- helpers for subclasses --------------------------------------------
    def to_numpy(
        self, df: DataFrame, features_col: Union[str, List[str]], label_col: Optional[str]
    ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Materialize the facade frame for the sklearn CPU baseline arm."""
        xs, ys = [], []
        for part in df.partitions:
            if isinstance(features_col, str):
                xs.append(np.asarray(list(part[features_col]), dtype=np.float64))
            else:
                xs.append(part[features_col].to_numpy(dtype=np.float64))
            if label_col:
                ys.append(part[label_col].to_numpy(dtype=np.float64))
        X = np.concatenate(xs)
        y = np.concatenate(ys) if ys else None
        return X, y

    def num_workers_arg(self) -> Dict[str, Any]:
        return (
            {"num_workers": self._args.num_devices} if self._args.num_devices > 0 else {}
        )
