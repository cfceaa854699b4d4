"""Corpora, eps calibration and query streams, made from the seed.

Copied from ``benchmarks/datasets.py`` so that later changes to the program
cannot move the benchmark's inputs; eps is calibrated here in float64 numpy
(the original calls the program's similarity on the device).

The paper's corpora (Deep1M, LAION-art, Text2Image) are not in the
repository. Each generator stands in for one at its width and metric:

  deep-like    l2   Gaussian mixture of 64 centres, mild clustering
  laion-like   cos  24 tight clusters, unit norm (the paper's dense case)
  txt2img-like ip   anisotropic heavy-tailed mixture

The corpus and the query pool are the deployment's, made from the
configuration's ``data_seed``; a run's seed orders the queries and draws
the arrivals. Seeds that made their own corpora asked for very different
work (a 2.7x range of ``qps`` over six seeds on one v5e), so each seed now
serves the same queries in another order.

Diversification levels follow the paper's phi(eps) calibration:
phi = expected degree of the diversity graph = (N-1) * P(sim > eps). The
paper's 10/100/500 at N = 10^6 are scaled to ``PHI_TARGETS``.
"""
from __future__ import annotations

import numpy as np

from bench.reference import sims64

PHI_TARGETS = dict(low=5.0, medium=50.0, high=200.0)


def make_dataset(name: str, n: int, d: int, seed: int):
    """``(x float32[n, d], metric)`` for one of the three generators."""
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
    raise KeyError(f"unknown dataset generator {name!r}")


def calibrate_eps(x, metric: str, phi: float, seed: int,
                  sample: int = 400_000) -> float:
    """eps such that E[deg(G^eps)] is about ``phi`` over the corpus, from
    a sample of random pairs (float64)."""
    rng = np.random.default_rng([seed, 1])
    n = x.shape[0]
    m = int(np.sqrt(sample))
    a = x[rng.integers(0, n, m)].astype(np.float64)
    b = x[rng.integers(0, n, m)].astype(np.float64)
    sims = np.concatenate([sims64(row, b, metric) for row in a])
    return float(np.quantile(sims, 1.0 - phi / (n - 1)))


def queries_for(x, num: int, rng, noise: float = 0.05):
    """``num`` queries: corpus rows with Gaussian noise of ``noise`` times
    their mean magnitude."""
    base = x[rng.integers(0, x.shape[0], num)]
    return (base + rng.normal(size=base.shape).astype(np.float32)
            * noise * np.abs(base).mean()).astype(np.float32)


def query_pool(x, size: int, data_seed: int, noise: float = 0.05):
    """The deployment's query pool: ``size`` queries drawn once from the
    configuration's data seed."""
    return queries_for(x, size, np.random.default_rng([data_seed, 9]), noise)


class QueryStream:
    """An endless stream over a fixed pool of queries: each pass over the
    pool in its own order, drawn from the seed. Every seed serves the same
    set of queries, so every seed asks for the same work."""

    def __init__(self, pool, seed: int):
        self.pool, self.seed = pool, seed
        self.orders: list = []

    def __getitem__(self, i: int):
        cycle, j = divmod(i, len(self.pool))
        while len(self.orders) <= cycle:
            rng = np.random.default_rng([self.seed, 5, len(self.orders)])
            self.orders.append(rng.permutation(len(self.pool)))
        return self.pool[self.orders[cycle][j]]
