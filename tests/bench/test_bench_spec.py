"""The harness finds a cell's parts by name, so a configuration, a traffic
mix or a metric is added by files alone; and it refuses to run without a
TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest
from bench_tiny import REPO, TINY_CONFIG, make_root, tiny_traffic

from bench import spec
from bench.record import RunRecord


def test_every_cell_of_the_benchmark_resolves():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["metric"] in ("l2", "cos", "ip")
        assert cell.traffic["loop"] in ("backlog", "open")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for kind, entries in (("end_to_end", cell.end_to_end),
                              ("layer_metrics", cell.per_layer)):
            for m in entries:
                assert callable(spec.load_reader(kind, m["name"]))


def test_config_mix_and_metric_added_as_files_alone(tmp_path):
    cfg = dict(TINY_CONFIG, d=24, n=777)
    mix = dict(tiny_traffic("open"), rate_per_s=3.5)
    root = make_root(tmp_path, configs={"added-cfg": cfg},
                     traffic={"added.mix": mix})
    reader = root / "bench" / "layer_metrics" / "added.requests.py"
    reader.write_text("def read(run):\n    return len(run.sent) or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "added.requests", "unit": "req", "better": "higher",
        "source": "host_clock", "layer": "scheduler", "moves": "qps",
        "workloads": ["added-cfg.added.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("added-cfg.added.mix", root)
    assert (cell.config["d"], cell.config["n"]) == (24, 777)
    assert cell.traffic["rate_per_s"] == 3.5
    names = [m["name"] for m in cell.per_layer]
    assert "added.requests" in names
    run = RunRecord(loop="open", num_lanes=4, setup_s=1.0, t_start=0.0,
                    t_end=1.0, sent=[object()] * 3)
    got = spec.read_metrics([m for m in cell.per_layer
                             if m["name"] == "added.requests"],
                            "layer_metrics", run, root)
    assert got == {"added.requests": {"value": 3.0, "unit": "req"}}
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell("missing.cell", root)


def test_reader_that_finds_nothing_is_left_out(tiny_root):
    cell = spec.load_cell("tiny.tb", tiny_root)
    run = RunRecord(loop="backlog", num_lanes=4, setup_s=2.0, t_start=0.0,
                    t_end=1.0, sent=[])
    got = spec.read_metrics(cell.per_layer, "layer_metrics", run, tiny_root)
    assert got == {}        # no trace, no spans, no answers: nothing to read
    e2e = spec.read_metrics(cell.end_to_end, "end_to_end", run, tiny_root)
    assert set(e2e) == {"qps", "setup_s"}   # no sample: no recall


def _run(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    cell = json.loads((REPO / "BENCHMARK.json").read_text())["workloads"][0]
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         cell["name"], "--seed", "3000000001",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_fails_without_a_tpu():
    p = _run(REPO)
    assert p.returncode == 2, p.stderr
    assert "no TPU" in p.stderr
    assert not p.stdout.strip().startswith("{")
    assert '"correct"' not in p.stdout


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for path in bench["paths"]:
        shutil.copytree(REPO / path, tmp_path / path,
                        ignore=shutil.ignore_patterns(".cache",
                                                      "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
