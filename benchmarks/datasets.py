"""Benchmark datasets: the paper's three metric spaces, synthetic stand-ins.

LAION-art / Deep1M / Txt2img are license/size-gated (DESIGN.md §7); we
substitute deterministic synthetic datasets with the same metric spaces and
density character, scaled to what 1 CPU core can index:

  deep-like    l2   uniform-ish Gaussian mixture, mild clustering
  laion-like   cos  heavy clustering (partially dense regions — the paper
                    calls out LAION's density as the hard case)
  txt2img-like ip   anisotropic heavy-tail mixture

Diversification levels follow the paper's phi(eps) calibration: phi(eps) =
expected diversity-graph degree = (N-1) * P(sim > eps); eps is chosen from a
random-pair similarity sample to hit the low/medium/high phi targets
(paper: 10/100/500 at N=1M; proportionally scaled here).

Graphs are HNSW (the paper's index) and cached on disk keyed by config.
"""
from __future__ import annotations

import os

import numpy as np

from repro.core.graph import FlatGraph, make_flat_graph

CACHE = os.path.join(os.path.dirname(__file__), "..", "results", "cache")

N_DEFAULT = 20_000
PHI_TARGETS = dict(low=5.0, medium=50.0, high=200.0)


def make_dataset(name: str, n: int = N_DEFAULT, d: int = 48,
                 seed: int = 0) -> tuple[np.ndarray, str]:
    rng = np.random.default_rng(seed)
    if name == "deep-like":
        centers = rng.normal(size=(64, d)) * 1.0
        x = centers[rng.integers(0, 64, n)] + rng.normal(size=(n, d)) * 0.7
        return x.astype(np.float32), "l2"
    if name == "laion-like":
        centers = rng.normal(size=(24, d)) * 2.0
        x = centers[rng.integers(0, 24, n)] + rng.normal(size=(n, d)) * 0.35
        x /= np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-9)
        return x.astype(np.float32), "cos"
    if name == "txt2img-like":
        scales = np.exp(rng.normal(size=(1, d)) * 0.8)
        centers = rng.normal(size=(32, d)) * scales
        x = centers[rng.integers(0, 32, n)] \
            + rng.normal(size=(n, d)) * 0.5 * scales
        return (x / np.sqrt(d)).astype(np.float32), "ip"
    raise KeyError(name)


DATASETS = ("deep-like", "laion-like", "txt2img-like")


def calibrate_eps(x: np.ndarray, metric: str, phi: float,
                  sample: int = 400_000, seed: int = 1) -> float:
    """eps such that E[deg(G^eps)] ~= phi over the dataset."""
    from repro.core.similarity import pairwise_sim
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    n = x.shape[0]
    m = int(np.sqrt(sample))
    a = x[rng.integers(0, n, m)]
    b = x[rng.integers(0, n, m)]
    sims = np.asarray(pairwise_sim(jnp.asarray(a), jnp.asarray(b),
                                   metric)).ravel()
    q = 1.0 - phi / (n - 1)
    return float(np.quantile(sims, q))


def queries_for(x: np.ndarray, num: int, seed: int = 7) -> np.ndarray:
    rng = np.random.default_rng(seed)
    base = x[rng.integers(0, x.shape[0], num)]
    return (base + rng.normal(size=base.shape).astype(np.float32)
            * 0.05 * np.abs(base).mean()).astype(np.float32)


def load_graph(name: str, n: int = N_DEFAULT, M: int = 12,
               ef_construction: int = 80, builder: str = "hnsw",
               seed: int = 0) -> tuple[FlatGraph, np.ndarray, str]:
    os.makedirs(CACHE, exist_ok=True)
    x, metric = make_dataset(name, n=n, seed=seed)
    key = f"{name}_{n}_{M}_{ef_construction}_{builder}_{seed}"
    path = os.path.join(CACHE, key + ".npz")
    if os.path.exists(path):
        z = np.load(path)
        g = make_flat_graph(x, z["neighbors"],
                            z["upper"] if z["upper"].size else None,
                            z["entry"], metric)
        return g, x, metric
    if builder == "hnsw":
        from repro.index.hnsw import build_hnsw
        g = build_hnsw(x, metric=metric, M=M,
                       ef_construction=ef_construction, seed=seed)
    else:
        from repro.index.flat import build_knn_graph
        g = build_knn_graph(x, metric=metric, M=M)
    np.savez(path, neighbors=np.asarray(g.neighbors),
             upper=np.asarray(g.upper), entry=np.asarray(g.entry))
    return g, x, metric
