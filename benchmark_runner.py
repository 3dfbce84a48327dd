"""Benchmark entry point (reference
``/root/reference/python/benchmark/benchmark_runner.py``), same CLI shape:

    python benchmark_runner.py <algorithm> [--platform cpu|tpu]
        [--mode tpu|cpu] [--num_chips N]
        [--num_rows N --num_cols D | --train_path dir] [algo flags...]

Supported algorithms: kmeans, knn, linear_regression, pca,
random_forest_classifier, random_forest_regressor, logistic_regression, umap.

``--platform cpu|tpu`` pins the jax backend in-process BEFORE any backend
touch (``spark_rapids_ml_tpu/utils/platform.py``); without it JAX reads
``JAX_PLATFORMS`` itself and otherwise takes the local TPU. The persistent
compile cache follows the one rule in ``utils/platform.enable_compile_cache``.
"""

import sys


def _pop_platform_flag(argv):
    """Extract --platform[=| ]VALUE from argv; returns (value_or_None, rest)."""
    rest = []
    value = None
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--platform":
            if i + 1 >= len(argv):
                sys.exit("--platform requires a value (cpu|tpu)")
            value = argv[i + 1]
            i += 2
            continue
        if a.startswith("--platform="):
            value = a.split("=", 1)[1]
            i += 1
            continue
        rest.append(a)
        i += 1
    return value, rest


def main() -> None:
    argv = sys.argv[1:]
    platform, argv = _pop_platform_flag(argv)

    # Pin before importing the bench modules (they import jax-using code).
    from spark_rapids_ml_tpu.utils.platform import (
        enable_compile_cache,
        pin_platform,
    )

    pin_platform(platform)
    enable_compile_cache()

    from benchmark.bench_kmeans import BenchmarkKMeans
    from benchmark.bench_linear_regression import BenchmarkLinearRegression
    from benchmark.bench_logistic_regression import BenchmarkLogisticRegression
    from benchmark.bench_nearest_neighbors import BenchmarkNearestNeighbors
    from benchmark.bench_pca import BenchmarkPCA
    from benchmark.bench_random_forest import (
        BenchmarkRandomForestClassifier,
        BenchmarkRandomForestRegressor,
    )
    from benchmark.bench_umap import BenchmarkUMAP

    registered = {
        "kmeans": BenchmarkKMeans,
        "knn": BenchmarkNearestNeighbors,
        "linear_regression": BenchmarkLinearRegression,
        "pca": BenchmarkPCA,
        "random_forest_classifier": BenchmarkRandomForestClassifier,
        "random_forest_regressor": BenchmarkRandomForestRegressor,
        "logistic_regression": BenchmarkLogisticRegression,
        "umap": BenchmarkUMAP,
    }

    if not argv or argv[0] in ("-h", "--help") or argv[0] not in registered:
        names = "\n    ".join(sorted(registered))
        print(f"usage: benchmark_runner.py <algorithm> [<args>]\n\nalgorithms:\n    {names}")
        sys.exit(0 if argv and argv[0] in ("-h", "--help") else 1)
    registered[argv[0]](argv[1:]).run()


if __name__ == "__main__":
    main()
