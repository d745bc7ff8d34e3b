#
# KMeans estimator/model.
#
# Capability parity with the reference's KMeans/KMeansModel
# (/root/reference/python/src/spark_rapids_ml/clustering.py:59-466): same
# Spark param mapping (clustering.py:61-82), same solver defaults
# (clustering.py:84-95), same model attributes (cluster_centers_, n_cols,
# dtype) and int prediction output (clustering.py:430-433).  The solver is
# the TPU-native shard_map Lloyd kernel in ops/kmeans.py instead of cuML
# KMeansMG over NCCL.
#

from __future__ import annotations

from typing import Any, Dict, List, Optional, Union

import numpy as np

import jax

from .. import profiling
from ..core import (
    FitInputs,
    _TpuEstimator,
    _TpuModelWithPredictionCol,
    fetch_fit_result,
    is_tall_table,
)
from ..dataframe import DataFrame
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    HasMaxIter,
    HasPredictionCol,
    HasSeed,
    HasTol,
    HasVerbose,
    HasWeightCol,
    Param,
    TypeConverters,
    _dummy,
    _TpuParams,
)
from ..ops.kmeans import (
    kmeans_predict_kernel,
    lloyd_iterations,
    lloyd_tall,
    random_init,
    random_init_tall,
    scalable_kmeans_pp_init,
)
from ..ops import lloyd_tall_pass
from ..utils import get_logger


class KMeansClass(_TpuParams):
    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        # mirrors clustering.py:61-82: distanceMeasure/weightCol unsupported,
        # initSteps/solver/maxBlockSizeInMB silently ignored
        return {
            "distanceMeasure": None,
            "initMode": "init",
            "k": "n_clusters",
            "initSteps": "",
            "maxIter": "max_iter",
            "seed": "random_state",
            "tol": "tol",
            "weightCol": None,
            "solver": "",
            "maxBlockSizeInMB": "",
        }

    @classmethod
    def _param_value_mapping(cls):
        return {
            "init": lambda v: {
                "k-means||": "scalable-k-means++",
                "random": "random",
                "scalable-k-means++": "scalable-k-means++",
            }.get(v)
        }

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "n_clusters": 8,
            "max_iter": 300,
            "tol": 0.0001,
            "verbose": False,
            "random_state": 1,
            "init": "scalable-k-means++",
            "n_init": 1,
            "oversampling_factor": 2.0,
            "max_samples_per_batch": 32768,
        }


class _KMeansParams(
    KMeansClass,
    HasFeaturesCol,
    HasFeaturesCols,
    HasPredictionCol,
    HasMaxIter,
    HasTol,
    HasSeed,
    HasWeightCol,
    HasVerbose,
):
    k = Param(_dummy(), "k", "The number of clusters to create. Must be > 1.", TypeConverters.toInt)
    initMode = Param(
        _dummy(),
        "initMode",
        'The initialization algorithm. Supported options: "random" and "k-means||".',
        TypeConverters.toString,
    )
    initSteps = Param(
        _dummy(), "initSteps", "The number of steps for k-means|| initialization mode. Must be > 0.", TypeConverters.toInt
    )
    distanceMeasure = Param(
        _dummy(), "distanceMeasure", "the distance measure", TypeConverters.toString
    )

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        super().__init__(*args, **kwargs)
        self._setDefault(
            k=2, initMode="k-means||", initSteps=2, maxIter=20, tol=0.0001
        )

    def getK(self) -> int:
        return self.getOrDefault("k")

    def setK(self, value: int):
        return self._set_params(k=value)

    def setInitMode(self, value: str):
        return self._set_params(initMode=value)

    def setMaxIter(self, value: int):
        return self._set_params(maxIter=value)

    def setTol(self, value: float):
        return self._set_params(tol=value)

    def setSeed(self, value: int):
        return self._set_params(seed=value)

    def setWeightCol(self, value: str):
        # parity with clustering.py setWeightCol: unsupported
        raise ValueError("'weightCol' is not supported.")


class KMeans(_KMeansParams, _TpuEstimator):
    """Distributed KMeans on a TPU mesh (Lloyd + k-means|| init), API-parity
    with the reference KMeans (clustering.py:146-308)."""

    # Lloyd has a pass over a feature-major table (ops/kmeans.lloyd_tall)
    _supports_tall_input = True

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._initialize_tpu_params()
        self._set_params(**kwargs)

    def _get_tpu_fit_func(self, dataset: DataFrame, extra_params=None):
        logger = get_logger(type(self))

        def _fit(inputs: FitInputs, params: Dict[str, Any]):
            # the table's type picks the pass: a feature-major TallMatrix takes
            # Lloyd's tall form, a row-major array the one it always took
            tall = is_tall_table(inputs.X)
            # the step spans below tile srml.fit (core.FitJob.run)
            with profiling.span("srml.fit.init"):
                k = int(params["n_clusters"])
                seed = int(params["random_state"]) & 0x7FFFFFFF
                chunk = min(int(params["max_samples_per_batch"]), inputs.X.shape[0])
                if tall:
                    if params["init"] != "random":
                        raise ValueError(
                            "KMeans on a feature-major table (ops.tall.TallMatrix) starts from "
                            "initMode='random'; k-means|| has no pass over that form yet"
                        )
                    centers0 = random_init_tall(inputs.X, inputs.weight, k, seed, inputs.mesh)
                    profiling.incr_counter("lloyd.tall_fits")
                    if lloyd_tall_pass.takes(inputs.X, k, chunk, inputs.mesh.devices.size):
                        # the update passes' whole tiles go through the kernel
                        profiling.incr_counter("lloyd.tall_kernel_fits")
                    profiling.incr_counter("lloyd.table_bytes", inputs.X.table_bytes)
                    profiling.incr_counter("lloyd.table_resident_bytes", inputs.X.resident_bytes)
                elif params["init"] == "random":
                    centers0 = random_init(inputs.X, inputs.weight, k, seed)
                else:
                    oversample = float(params["oversampling_factor"])
                    round_size = max(1, min(int(oversample * k), inputs.n_rows))
                    centers0 = scalable_kmeans_pp_init(
                        inputs.X,
                        inputs.weight,
                        k,
                        seed,
                        oversample,
                        rounds=4,
                        round_size=round_size,
                    )
            with profiling.span("srml.fit.solve"):
                solved = (lloyd_tall if tall else lloyd_iterations)(
                    inputs.X,
                    inputs.weight,
                    centers0,
                    inputs.mesh,
                    int(params["max_iter"]),
                    float(params["tol"]),
                    chunk,
                )
            # ONE batched device fetch: int()/float()/np.asarray each cost
            # a host round-trip, and centers/n_iter/inertia are ready
            # together
            centers_h, n_iter_h, inertia_h = fetch_fit_result(solved)
            with profiling.span("srml.fit.pack"):
                # the device buffers go inside a step, not with the frame
                # after the last one
                del solved, centers0
                logger.info(
                    "iterations: %d, inertia: %f", int(n_iter_h), float(inertia_h)
                )
                return {
                    "cluster_centers_": np.asarray(centers_h, dtype=np.float64),
                    "n_cols": inputs.n_cols,
                    "dtype": str(inputs.dtype),
                    "n_iter_": int(n_iter_h),
                    "inertia_": float(inertia_h),
                }

        return _fit

    def _create_model(self, result: Dict[str, Any]) -> "KMeansModel":
        return KMeansModel(**result)

    def streaming(self):
        """Streaming incremental-fit engine over this configured estimator:
        mini-batch Lloyd with count-weighted per-center merge —
        partial_fit/merge/finalize (srml-stream, docs/streaming.md)."""
        from ..stream.engines import StreamingKMeans

        return StreamingKMeans(self)


class KMeansModel(_KMeansParams, _TpuModelWithPredictionCol):
    # cluster ids are integral (Spark KMeansModel emits IntegerType)
    _OUT_COLUMN_DDL = {
        **_TpuModelWithPredictionCol._OUT_COLUMN_DDL, "predictionCol": "int"
    }

    def __init__(
        self,
        cluster_centers_: np.ndarray,
        n_cols: int,
        dtype: str,
        n_iter_: int = 0,
        inertia_: float = 0.0,
    ) -> None:
        super().__init__(
            cluster_centers_=np.asarray(cluster_centers_),
            n_cols=int(n_cols),
            dtype=str(dtype),
            n_iter_=int(n_iter_),
            inertia_=float(inertia_),
        )
        self.cluster_centers_ = np.asarray(cluster_centers_)
        self.n_cols = int(n_cols)
        self.dtype = str(dtype)
        self.n_iter_ = int(n_iter_)
        self.inertia_ = float(inertia_)

    def clusterCenters(self) -> List[np.ndarray]:
        """Parity with Spark KMeansModel.clusterCenters (clustering.py:385-391)."""
        return list(self.cluster_centers_)

    @property
    def hasSummary(self) -> bool:
        return False

    def predict(self, value: np.ndarray) -> int:
        """Single-vector prediction (Spark API parity); same dtype policy as
        transform() so the two paths agree on borderline points."""
        np_dtype = self._transform_dtype(self.dtype)
        arr = np.asarray(value, dtype=np_dtype)[None, :]
        return int(
            np.asarray(
                kmeans_predict_kernel(
                    jax.numpy.asarray(arr),
                    jax.numpy.asarray(self.cluster_centers_.astype(np_dtype)),
                )
            )[0]
        )

    def cpu(self):
        """pyspark.ml KMeansModel (parity hook for clustering.py:393-435)."""
        from ..spark.interop import to_spark_kmeans_model

        return to_spark_kmeans_model(self)

    def _get_tpu_transform_func(self, dataset: DataFrame):
        np_dtype = self._transform_dtype(self.dtype)
        centers = jax.device_put(np.asarray(self.cluster_centers_, dtype=np_dtype))
        pred_col = self.getOrDefault("predictionCol")
        predict = jax.jit(kmeans_predict_kernel)

        def _transform(features: np.ndarray) -> Dict[str, Any]:
            labels = predict(jax.device_put(np.asarray(features, dtype=np_dtype)), centers)
            return {pred_col: np.asarray(labels)}

        return _transform

    def _serving_entry(self, mesh: Any = None):
        """Online inference hook (serving/): nearest-center assignment as a
        single bucket-padded kernel through the AOT executable cache."""
        from ..serving.entry import kernel_entry

        np_dtype = self._transform_dtype(self.dtype)
        centers = jax.device_put(np.asarray(self.cluster_centers_, dtype=np_dtype))
        pred_col = self.getOrDefault("predictionCol")
        return kernel_entry(
            "serve.kmeans",
            jax.jit(kmeans_predict_kernel),
            (centers,),
            {},
            lambda labels: {pred_col: np.asarray(labels)},
            dtype=np_dtype,
            n_cols=self.n_cols,
            out_cols=[pred_col],
            info={"k": len(self.cluster_centers_)},
            mesh=mesh,
        )

    def _lane_entry(self, mesh: Any = None):
        """Multiplexed serving hook (serving/multiplex): this model's
        centers as ONE lane of the lane-stacked nearest-center kernel —
        variants must share k (the leaf-shape check in lane_signature
        enforces it)."""
        from ..ops.kmeans import lane_kmeans_predict_kernel
        from ..serving.multiplex import LaneEntry

        np_dtype = self._transform_dtype(self.dtype)
        centers = np.ascontiguousarray(
            np.asarray(self.cluster_centers_, dtype=np_dtype)
        )
        pred_col = self.getOrDefault("predictionCol")
        return LaneEntry(
            name="lanes.kmeans",
            n_cols=self.n_cols,
            dtype=np_dtype,
            out_cols=[pred_col],
            leaves=(centers,),
            kernel=lane_kmeans_predict_kernel,
            statics={},
            postprocess=lambda labels: {pred_col: np.asarray(labels)},
            info={"k": len(self.cluster_centers_)},
        )
