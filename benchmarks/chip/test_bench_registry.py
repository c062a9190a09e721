"""A configuration, traffic mix or metric is a file found by its name:
adding one needs no edit to any file that is already there."""
import json
import shutil

import numpy as np

from benchmarks.chip import harness
from benchmarks.chip.tiny import ROOT, tiny_run


def test_new_files_are_found_by_name(tmp_path):
    for kind in ("configs", "traffic", "metrics"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "newcfg.json").write_text(
        json.dumps({"corpus": {"n": 7}}))
    (tmp_path / "traffic" / "newmix.json").write_text(
        json.dumps({"reads": {"rate_per_s": 3, "order": "distinct"}}))
    (tmp_path / "metrics" / "new.metric_ms.py").write_text(
        "def read(run):\n    return 2.0 * run.seconds\n")
    assert harness.load_json("configs", "newcfg", tmp_path)["corpus"]["n"] == 7
    assert harness.load_json("traffic", "newmix",
                             tmp_path)["reads"]["rate_per_s"] == 3
    mod = harness.load_metric("new.metric_ms", tmp_path)
    run = harness.Run(seconds=4.0, setup_s=1.0, due=np.zeros(0),
                      done=np.zeros(0), est=np.zeros(0), prov=[],
                      nvisited=[])
    assert mod.read(run) == 8.0


def test_new_config_mixes_and_metric_run_without_edits(tmp_path):
    """A checkout with a configuration, two mixes (on/off bursts, a closed
    loop) and a metric that no module names runs both new cells."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = json.loads((chip / "configs" / "sift1m.json").read_text())
    del config["tiny"]                 # cut by the default tiny size
    config["corpus"].update(d=24, data_seed=5)
    config["prober"]["pq_m"] = 6
    (chip / "configs" / "other24.json").write_text(json.dumps(config))
    (chip / "traffic" / "bursty.json").write_text(json.dumps(
        {"reads": {"rate_per_s": 50, "order": "distinct",
                   "burst": {"on_s": 0.5, "off_s": 0.5}}}))
    (chip / "traffic" / "closed8.json").write_text(json.dumps(
        {"reads": {"rate_per_s": 400, "order": "distinct",
                   "outstanding": 8}}))
    (chip / "metrics" / "window.answered_share.py").write_text(
        "def read(run):\n"
        "    return 100.0 * run.answered.mean() if len(run.due) else None\n")
    bench = {
        "configs": [{"name": "other24",
                     "file": "benchmarks/chip/configs/other24.json"}],
        "workloads": [{"name": "other24.bursty", "config": "other24",
                       "traffic": "bursty", "chips": 1},
                      {"name": "other24.closed8", "config": "other24",
                       "traffic": "closed8", "chips": 1}],
        "end_to_end": [{"name": "estimates_per_s", "unit": "estimates/s"},
                       {"name": "window.answered_share", "unit": "%"}],
        "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for cell in ("other24.bursty", "other24.closed8"):
        out = tiny_run(cell, root=tmp_path, rate=None)
        assert out["correct"], (cell, out["checks"])
        assert out["metrics"]["window.answered_share"]["value"] == 100.0
        assert out["attempted"] > 0 and out["failed"] == 0


def test_cell_metrics_follow_benchmark_json():
    bench = {
        "end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["w2"]}],
        "per_layer": [{"name": "x", "moves": "a"},
                      {"name": "y", "moves": "b"},
                      {"name": "z", "moves": "a", "workloads": ["w2"]}],
    }
    names = lambda c, k: [m["name"] for m in harness.cell_metrics(bench, c, k)]
    assert names("w1", "end_to_end") == ["a"]
    assert names("w2", "end_to_end") == ["a", "b"]
    assert names("w1", "per_layer") == ["x"]
    assert names("w2", "per_layer") == ["x", "y", "z"]


def test_every_named_file_exists():
    from benchmarks.chip.tiny import bench
    b = bench()
    for w in b["workloads"]:
        harness.load_json("traffic", w["traffic"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(harness.load_metric(m["name"]).read)
