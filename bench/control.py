"""The control: the reference put in the program's place, one precision
step down, which the comparison that decides ``correct`` has to fail.

The configurations state float32 similarities with matrix products at
``Precision.HIGHEST``; the step below is ``high``: three bfloat16 passes
(``hi*hi + hi*lo + lo*hi``, each operand split into a bfloat16 ``hi`` and
the bfloat16 rounding of its remainder ``lo``, products summed in
float32). That is what XLA does on a TPU for ``Precision.HIGH``; here it is
emulated in numpy, so that it reads the same on any machine. The control
answers each sampled query with exact top-X plus div-A* over those
similarities (``reference.host_oracle``), and its answers go through the
same comparison as the program's (``bench.run.checks_of``).

``--kind half_rows`` puts a search fault in the program's place instead:
the float64 reference whose search reads every other row of the corpus
only, as a broken gather would. It names real rows with their true scores,
but worse ones than the search should find; the comparison has to fail it
too (``suboptimal``).

    python -m bench.control --workload <cell> --seeds 1 2 3 [--kind half_rows]

prints, per seed, the compared numbers beside the cell's limits, with
queries that the cell's own run would sample at that seed.
"""
from __future__ import annotations

import argparse
import json
import sys

import ml_dtypes
import numpy as np

from bench import data as D
from bench import reference as R
from bench import spec


def _split(a):
    a = np.asarray(a, np.float32)
    hi = a.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (a - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def dot_high(a, b):
    """``a[m, d] @ b[n, d].T`` in three bfloat16 passes, float32 sums."""
    ah, al = _split(a)
    bh, bl = _split(b)
    return ah @ bh.T + (ah @ bl.T + al @ bh.T)


def _sims(dots, aa, bb, metric):
    if metric == "ip":
        return dots
    if metric == "cos":
        return dots / np.maximum(np.sqrt(aa)[:, None] * np.sqrt(bb)[None, :],
                                 np.float32(1e-12))
    return 1.0 - np.sqrt(np.maximum(aa[:, None] + bb[None, :] - 2.0 * dots,
                                    0.0))


def sims_high(q, x, metric):
    q = np.asarray(q, np.float32)[None]
    x = np.asarray(x, np.float32)
    return _sims(dot_high(q, x), (q * q).sum(1), (x * x).sum(1), metric)[0]


def pair_high(a, metric):
    a = np.asarray(a, np.float32)
    aa = (a * a).sum(1)
    return _sims(dot_high(a, a), aa, aa, metric)


def control_answer(x64, metric, q, k, eps) -> dict:
    ids, _, frontier, scores, ok = R.host_oracle(
        x64, metric, q, k, eps, sims_fn=sims_high, pair_fn=pair_high)
    return dict(ids=ids, scores=scores, certified=ok, frontier=frontier)


def half_rows_answer(x64, metric, q, k, eps) -> dict:
    rows = np.arange(0, x64.shape[0], 2)
    ids, _, frontier, scores, ok = R.host_oracle(x64[rows], metric, q, k,
                                                 eps)
    return dict(ids=rows[ids], scores=scores, certified=ok,
                frontier=rows[frontier])


ANSWERS = {"control": control_answer, "half_rows": half_rows_answer}


def control_readings(cell, seed: int, kind: str = "control",
                     run_seconds: float | None = None) -> dict:
    """The compared numbers, each beside its limit, of the control (or the
    fault ``kind``) at one seed, over queries that the cell's run samples
    (``bench.run.sample_indices``, from as many requests as a warm-up pass
    of ``run_seconds`` offers)."""
    from bench.run import checks_of, rehearsal_count, sample_indices
    if run_seconds is None:
        run_seconds = spec.load_json(spec.ROOT / "BENCHMARK.json")[
            "run_seconds"]
    answer = ANSWERS[kind]
    cfg, traffic = cell.config, cell.traffic
    data_seed = int(cfg["data_seed"])
    x, metric = D.make_dataset(cfg["dataset"], cfg["n"], cfg["d"], data_seed)
    x64 = x.astype(np.float64)
    k = int(traffic["k"])
    eps = D.calibrate_eps(x, metric, D.PHI_TARGETS[traffic["phi"]],
                          data_seed)
    stream = D.QueryStream(D.query_pool(x, int(cfg["query_pool"]),
                                        data_seed,
                                        float(cfg["query_noise"])), seed)
    rows = []
    sent = rehearsal_count(traffic, run_seconds)
    for i in sample_indices(seed, traffic, sent):
        q = stream[int(i)]
        rows.append(R.check_answer(answer(x64, metric, q, k, eps),
                                   q, x64, metric, k, eps))
    return checks_of(R.summarize(rows), 0, cfg["limits"], eps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--kind", choices=sorted(ANSWERS), default="control")
    args = ap.parse_args(argv)
    from bench.run import correct_of
    cell = spec.load_cell(args.workload)
    for seed in args.seeds:
        checks = control_readings(cell, seed, args.kind)
        print(json.dumps({"workload": args.workload, "kind": args.kind,
                          "seed": seed, "checks": checks,
                          "correct": correct_of(checks)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
