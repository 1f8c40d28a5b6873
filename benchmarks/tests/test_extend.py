"""A later PR adds a configuration, a cell and a per-layer metric as new
files plus entries in BENCHMARK.json, and edits no file that is there: done
here in a temporary copy of the benchmark."""

import json
import os
import shutil
import subprocess
import sys

from benchmarks import harness


def test_add_config_cell_and_metric_as_files(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(harness.ROOT, "bcfl_tpu"), os.path.join(root, "bcfl_tpu"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    b = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))

    def dump(obj, *parts):
        with open(os.path.join(root, "benchmarks", *parts), "w") as f:
            json.dump(obj, f)

    # a configuration: biobert-base (cased BERT-base, vocabulary 28996)
    cfg = harness.load_json("configs", "bert-base.json")
    cfg.update(name="biobert-base", vocab_size=28996, program_model="biobert-base",
               source="https://huggingface.co/dmis-lab/biobert-v1.1")
    cfg["plumbing"] = dict(cfg["plumbing"], vocab_size=4096)
    dump(cfg, "configs", "biobert-base.json")
    # a cell: three clients, unweighted mean (a FedConfig field no cell sets today)
    cell = harness.load_json("workloads", "bert-base.fedavg-s128.json")
    cell.update(name="biobert-base.fedavg3-s128", config="biobert-base", traffic_name="fedavg3-s128")
    cell["traffic"] = dict(cell["traffic"], clients=3)
    cell["fed"] = dict(cell["fed"], weighted_agg=False)
    dump(cell, "workloads", "biobert-base.fedavg3-s128.json")
    # a per-layer metric with a reader of its own
    with open(os.path.join(root, "benchmarks", "readers", "mine.py"), "w") as f:
        f.write("def rounds_per_dispatch(ctx):\n    return float(ctx['k'])\n")
    spec = {"name": "engine.rounds_per_dispatch", "unit": "rounds", "better": "higher",
            "source": "program_counter", "layer": "engine", "moves": "tokens_per_s_per_chip",
            "reader": "readers/mine.py:rounds_per_dispatch"}
    dump(spec, "metrics", "engine.rounds_per_dispatch.json")
    b["configs"].append({"name": "biobert-base", "source": cfg["source"],
                         "file": "benchmarks/configs/biobert-base.json",
                         "reduced": cfg["reduced"], "why": "test"})
    b["workloads"].append({"name": cell["name"], "config": "biobert-base",
                           "traffic": "fedavg3-s128", "chips": 1, "why": "test"})
    b["per_layer"].append({k: spec[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
                          | {"workloads": [cell["name"]]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), "--workload", cell["name"],
         "--seed", "99", "--seconds", "1", "--trace", "1", "--plumbing"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root,
        timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True
    assert r["metrics"]["engine.rounds_per_dispatch"] == {"value": 4.0, "unit": "rounds"}
    assert "engine.fused_round_pct" in r["metrics"]
    for path, content in before.items():  # no file that was there has changed
        assert open(path, "rb").read() == content, path
