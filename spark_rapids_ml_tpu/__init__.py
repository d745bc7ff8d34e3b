#
# spark_rapids_ml_tpu: a TPU-native distributed classical-ML framework with
# the capabilities of NVIDIA's spark-rapids-ml (reference mounted at
# /root/reference), rebuilt on jax/XLA/pjit: estimators dispatch to jax.jit'd
# solvers sharded over a device Mesh instead of cuML MG kernels over NCCL.
#
from . import profiling
from .version import __version__

__all__ = [
    "__version__",
    "KMeans",
    "KMeansModel",
    "PCA",
    "PCAModel",
    "LinearRegression",
    "LinearRegressionModel",
    "LogisticRegression",
    "LogisticRegressionModel",
    "RandomForestClassifier",
    "RandomForestClassificationModel",
    "RandomForestRegressor",
    "RandomForestRegressionModel",
    "NearestNeighbors",
    "NearestNeighborsModel",
    "ApproximateNearestNeighbors",
    "ApproximateNearestNeighborsModel",
    "UMAP",
    "UMAPModel",
    "CrossValidator",
    "Pipeline",
    "PipelineModel",
    "StreamingSession",
    "streaming_fit",
]


# import.us: microseconds this process spent importing the package through
# its public names — the package's own import below (from profiling's first
# line on) and every lazy re-export's module, which is where the seconds
# are.  A module imported by its own path is not in it.
_importing = 0


def _timed_import(location):
    from importlib import import_module

    global _importing
    t0 = profiling.now()
    _importing += 1
    try:
        return import_module(location, __name__)
    finally:
        _importing -= 1
        if not _importing:  # a re-export that imports another counts once
            profiling.incr_counter(
                "import.us", int(round(1e6 * (profiling.now() - t0)))
            )


def __getattr__(name):  # lazy re-exports keep `import spark_rapids_ml_tpu` light
    _locations = {
        "KMeans": ".models.kmeans",
        "KMeansModel": ".models.kmeans",
        "PCA": ".models.pca",
        "PCAModel": ".models.pca",
        "LinearRegression": ".models.linear_regression",
        "LinearRegressionModel": ".models.linear_regression",
        "LogisticRegression": ".models.logistic_regression",
        "LogisticRegressionModel": ".models.logistic_regression",
        "RandomForestClassifier": ".models.random_forest",
        "RandomForestClassificationModel": ".models.random_forest",
        "RandomForestRegressor": ".models.random_forest",
        "RandomForestRegressionModel": ".models.random_forest",
        "NearestNeighbors": ".models.knn",
        "NearestNeighborsModel": ".models.knn",
        "ApproximateNearestNeighbors": ".models.approximate_nn",
        "ApproximateNearestNeighborsModel": ".models.approximate_nn",
        "UMAP": ".models.umap",
        "UMAPModel": ".models.umap",
        "CrossValidator": ".tuning",
        "Pipeline": ".pipeline",
        "PipelineModel": ".pipeline",
        "StreamingSession": ".stream",
        "streaming_fit": ".stream",
    }
    if name in _locations:
        try:
            return getattr(_timed_import(_locations[name]), name)
        except ModuleNotFoundError as e:
            raise AttributeError(
                f"module {__name__!r} has no attribute {name!r} ({e})"
            ) from e
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():  # surface the lazy re-exports to dir()/completion
    return sorted(set(globals()) | set(__all__))


profiling.incr_counter(
    "import.us", int(round(1e6 * (profiling.now() - profiling._EPOCH)))
)
