#
# PCA estimator/model.
#
# Capability parity with the reference's PCA/PCAModel
# (/root/reference/python/src/spark_rapids_ml/feature.py:61-440): same Spark
# param surface ({k: n_components} mapping, feature.py:62-65; solver defaults
# feature.py:66-73), same model attributes (mean_, components_,
# explained_variance[_ratio]_, singular_values_, n_cols, dtype), and the same
# Spark-parity transform semantics (no mean removal at transform time,
# feature.py:419-431).  The solver itself is TPU-native: a single jitted
# covariance + eigh kernel over a row-sharded mesh (ops/linalg.py) instead of
# cuML PCAMG over NCCL.
#

from __future__ import annotations

from typing import Any, Dict, List, Optional, Union

import numpy as np

import jax

from ..core import (
    FitInputs,
    _TpuEstimator,
    _TpuModel,
)
from ..dataframe import DataFrame
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    HasInputCol,
    HasInputCols,
    HasOutputCol,
    HasVerbose,
    Param,
    TypeConverters,
    _dummy,
    _TpuParams,
)
from ..ops.linalg import pca_fit, pca_transform_kernel
from ..parallel.mesh import data_sharding


class PCAClass(_TpuParams):
    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {"k": "n_components"}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "n_components": None,
            "svd_solver": "auto",
            "verbose": False,
            "whiten": False,
        }


class _PCAParams(PCAClass, HasInputCol, HasInputCols, HasOutputCol, HasVerbose):
    k = Param(
        _dummy(),
        "k",
        "the number of principal components (> 0)",
        TypeConverters.toInt,
    )

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        super().__init__(*args, **kwargs)
        self._setDefault(inputCol="features", outputCol="pca_features")

    def getK(self) -> int:
        return self.getOrDefault("k")

    def setK(self, value: int):
        return self._set_params(k=value)

    def setInputCol(self, value: Union[str, List[str]]):
        if isinstance(value, str):
            self._set_params(inputCol=value)
        else:
            self._set_params(inputCols=value)
        return self

    def setInputCols(self, value: List[str]):
        return self._set_params(inputCols=value)

    def setOutputCol(self, value: str):
        return self._set_params(outputCol=value)


class PCA(_PCAParams, _TpuEstimator):
    """Distributed PCA on a TPU mesh.

    The fit is one jitted kernel: weighted scatter/mean over the row-sharded
    dataset (psum over ICI/DCN), replicated (D, D) eigh, deterministic
    component signs.  Mirrors the reference's API (feature.py:106-305).
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._initialize_tpu_params()
        self._set_params(**kwargs)

    def _get_tpu_fit_func(self, dataset: DataFrame, extra_params=None):
        def _fit(inputs: FitInputs, params: Dict[str, Any]):
            k = params.get("n_components") or min(inputs.n_rows, inputs.n_cols)
            k = min(int(k), inputs.n_cols)
            # whiten is honored at transform time (see PCAModel); wide inputs
            # route the eigh through the native host runtime (ops.linalg.pca_fit)
            mean, components, var, ratio, sv = pca_fit(
                inputs.X, inputs.weight, k, mesh=inputs.mesh
            )
            return {
                "mean_": np.asarray(mean, dtype=np.float64),
                "components_": np.asarray(components, dtype=np.float64),
                "explained_variance_": np.asarray(var, dtype=np.float64),
                "explained_variance_ratio_": np.asarray(ratio, dtype=np.float64),
                "singular_values_": np.asarray(sv, dtype=np.float64),
                "n_cols": inputs.n_cols,
                "dtype": str(inputs.dtype),
            }

        return _fit

    def _create_model(self, result: Dict[str, Any]) -> "PCAModel":
        return PCAModel(**result)

    def streaming(self):
        """Streaming incremental-fit engine over this configured estimator:
        mergeable covariance-moment accumulation, finalized through the
        batch kernel's shared eigh derivation — partial_fit/merge/finalize
        (srml-stream, docs/streaming.md)."""
        from ..stream.engines import StreamingPCA

        return StreamingPCA(self)


class PCAModel(_PCAParams, _TpuModel):
    def __init__(
        self,
        mean_: np.ndarray,
        components_: np.ndarray,
        explained_variance_: np.ndarray,
        explained_variance_ratio_: np.ndarray,
        singular_values_: np.ndarray,
        n_cols: int,
        dtype: str,
    ) -> None:
        super().__init__(
            mean_=np.asarray(mean_),
            components_=np.asarray(components_),
            explained_variance_=np.asarray(explained_variance_),
            explained_variance_ratio_=np.asarray(explained_variance_ratio_),
            singular_values_=np.asarray(singular_values_),
            n_cols=int(n_cols),
            dtype=str(dtype),
        )
        self.mean_ = np.asarray(mean_)
        self.components_ = np.asarray(components_)
        self.explained_variance_ = np.asarray(explained_variance_)
        self.explained_variance_ratio_ = np.asarray(explained_variance_ratio_)
        self.singular_values_ = np.asarray(singular_values_)
        self.n_cols = int(n_cols)
        self.dtype = str(dtype)
        self._set_params(k=len(self.components_))

    # -- reference-parity accessors (feature.py:336-360) -------------------
    @property
    def mean(self) -> List[float]:
        return self.mean_.tolist()

    @property
    def pc(self) -> np.ndarray:
        """Principal components, one per *column* (Spark DenseMatrix layout)."""
        return self.components_.T

    @property
    def explainedVariance(self) -> np.ndarray:
        return self.explained_variance_ratio_

    def cpu(self):
        """Return the equivalent pyspark.ml PCAModel (requires pyspark +
        an active SparkSession; parity hook for feature.py:362-376)."""
        from ..spark.interop import to_spark_pca_model

        return to_spark_pca_model(self)

    def _out_columns(self) -> List[str]:
        return [self.getOrDefault("outputCol")]

    def _get_tpu_transform_func(self, dataset: DataFrame):
        np_dtype = self._transform_dtype(self.dtype)
        comps = np.asarray(self.components_, dtype=np_dtype)
        if self._tpu_params.get("whiten"):
            # whitened projection: unit variance per component (note: Spark
            # semantics never center at transform time, so whitening scales
            # the uncentered projection)
            scale = 1.0 / np.sqrt(
                np.maximum(self.explained_variance_, 1e-12)
            ).astype(np_dtype)
            comps = comps * scale[:, None]
        components = jax.device_put(comps)
        out_col = self.getOrDefault("outputCol")

        def _transform(features: np.ndarray) -> Dict[str, Any]:
            projected = pca_transform_kernel(
                jax.device_put(np.asarray(features, dtype=np_dtype)), components
            )
            return {out_col: np.asarray(projected)}

        return _transform

    def _serving_entry(self, mesh: Any = None):
        """Online inference hook (serving/): the (whiten-scaled) projection
        as one bucket-padded kernel through the AOT executable cache —
        exactly the matrix transform() applies, so served and batch outputs
        are bit-identical."""
        from ..serving.entry import kernel_entry

        np_dtype = self._transform_dtype(self.dtype)
        comps = np.asarray(self.components_, dtype=np_dtype)
        if self._tpu_params.get("whiten"):
            scale = 1.0 / np.sqrt(
                np.maximum(self.explained_variance_, 1e-12)
            ).astype(np_dtype)
            comps = comps * scale[:, None]
        components = jax.device_put(comps)
        out_col = self.getOrDefault("outputCol")
        return kernel_entry(
            "serve.pca",
            pca_transform_kernel,  # module-level @jax.jit
            (components,),
            {},
            lambda proj: {out_col: np.asarray(proj)},
            dtype=np_dtype,
            n_cols=self.n_cols,
            out_cols=[out_col],
            info={"k": len(self.components_)},
            mesh=mesh,
        )

    def _lane_entry(self, mesh: Any = None):
        """Multiplexed serving hook (serving/multiplex): the
        (whiten-scaled) component matrix as ONE lane of the lane-stacked
        projection kernel — the whiten scale is folded host-side exactly
        as in the dedicated entry, so the lane kernel stays a pure
        gathered matmul."""
        from ..ops.linalg import lane_pca_transform_kernel
        from ..serving.multiplex import LaneEntry

        np_dtype = self._transform_dtype(self.dtype)
        comps = np.asarray(self.components_, dtype=np_dtype)
        if self._tpu_params.get("whiten"):
            scale = 1.0 / np.sqrt(
                np.maximum(self.explained_variance_, 1e-12)
            ).astype(np_dtype)
            comps = comps * scale[:, None]
        out_col = self.getOrDefault("outputCol")
        return LaneEntry(
            name="lanes.pca",
            n_cols=self.n_cols,
            dtype=np_dtype,
            out_cols=[out_col],
            leaves=(np.ascontiguousarray(comps),),
            kernel=lane_pca_transform_kernel,
            statics={},
            postprocess=lambda proj: {out_col: np.asarray(proj)},
            info={"k": len(self.components_)},
        )
