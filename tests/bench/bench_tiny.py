"""A tiny copy of the benchmark's layout, for driving a run on the CPU."""
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "source": "test", "dataset": "deep-like", "data_seed": 0, "d": 16,
    "metric": "l2",
    "n": 1024, "M": 8, "builder": "knng", "num_lanes": 4, "max_k": 8,
    "default_ef": 16, "capacity0": 256, "query_pool": 256,
    "query_noise": 0.05,
    "limits": {"score_err": 1e-3, "suboptimal": 4, "recheck_fail": 0},
}
#: the scheduler's prewarm, cut to the tiny cell's capacity
TINY_SCHEDULER = {"prewarm_capacity": 256}


def tiny_traffic(loop):
    t = {"loop": loop, "k": 5, "phi": "high", "check_sample": 8}
    if loop == "backlog":
        t["pending_per_lane"] = 2
        t["rehearse_per_s"] = 40
    else:
        t["rate_per_s"] = 6.0
    return t


def make_root(root: Path, configs=None, traffic=None) -> Path:
    """``root`` with BENCHMARK.json, the real readers, and the given
    configs and mixes; one cell per (config, mix) pair."""
    configs = configs or {"tiny": TINY_CONFIG}
    traffic = traffic or {"tb": tiny_traffic("backlog"),
                          "to": tiny_traffic("open")}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for kind in ("end_to_end", "layer_metrics"):
        shutil.copytree(REPO / "bench" / kind, root / "bench" / kind)
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    for name, cfg in configs.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    for name, mix in traffic.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    bench["configs"] = [{"name": n, "source": "test", "reduced": [],
                         "why": "test", "file": f"bench/configs/{n}.json"}
                        for n in configs]
    bench["workloads"] = [{"name": f"{c}.{t}", "config": c, "traffic": t,
                           "chips": 1, "why": "test"}
                          for c in configs for t in traffic]
    names = {m["name"] for m in bench["end_to_end"]}
    bench["end_to_end"] += [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock"}
        for n in ("p50_ms", "p95_ms") if n not in names]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
