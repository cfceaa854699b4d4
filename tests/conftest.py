import numpy as np
import pytest


@pytest.fixture(scope="session")
def clustered_data():
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(12, 24)) * 2.0
    x = (centers[rng.integers(0, 12, 600)]
         + rng.normal(size=(600, 24)) * 0.3).astype(np.float32)
    return x


@pytest.fixture(scope="session")
def small_graph(clustered_data):
    from repro.index.flat import build_knn_graph

    return build_knn_graph(clustered_data, metric="l2", M=8)


@pytest.fixture(scope="session")
def small_graph_cos(clustered_data):
    from repro.index.flat import build_knn_graph

    return build_knn_graph(clustered_data, metric="cos", M=8)
