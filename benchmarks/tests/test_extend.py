"""A later PR adds a configuration, a cell and a per-layer metric, or a whole
model family with its reference and its required operations, as new files
plus entries in BENCHMARK.json, and edits no file that is there: done here
in a temporary copy of the benchmark."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "decoder_lora")


def copy_of_the_benchmark(root):
    """``benchmarks/`` (without its tests) and the program under ``root``;
    returns every file's content as it stands."""
    shutil.copytree(os.path.join(harness.ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(harness.ROOT, "bcfl_tpu"), os.path.join(root, "bcfl_tpu"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    return before


def unchanged(root, before, bench_before):
    """No file that was there has changed, and BENCHMARK.json has only grown."""
    for path, content in before.items():
        assert open(path, "rb").read() == content, path
    after = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for key, value in bench_before.items():
        assert after[key][:len(value)] == value if isinstance(value, list) else after[key] == value


def test_add_config_cell_and_metric_as_files(tmp_path):
    root = str(tmp_path)
    before = copy_of_the_benchmark(root)
    b = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    bench_before = json.loads(json.dumps(b))

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
    unchanged(root, before, bench_before)


# ------------------------------------------------------------ a whole family

def add_the_family(root):
    """A decoder under LoRA as a causal-LM job (data/decoder_lora): the
    family's package, a configuration, a cell, a metric and its reader, all
    new files, and their entries in BENCHMARK.json. Returns the cell's name."""
    B = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(DATA, "family"), os.path.join(B, "families", "decoder_lora"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for src, dst in (("config.json", "configs/tiny-llama-lora.json"),
                     ("cell.json", "workloads/tiny-llama-lora.lm-s16.json"),
                     ("metric.json", "metrics/lora.merge_ms_per_round.json"),
                     ("reader.py", "readers/lora.py")):
        assert not os.path.exists(os.path.join(B, dst))
        shutil.copy(os.path.join(DATA, src), os.path.join(B, dst))
    shutil.copy(os.path.join(DATA, "faults.py"), os.path.join(root, "faults.py"))  # beside, not in
    cfg, cell, m = (json.load(open(os.path.join(DATA, f)))
                    for f in ("config.json", "cell.json", "metric.json"))
    b = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    b["configs"].append({"name": cfg["name"], "source": cfg["source"], "reduced": cfg["reduced"],
                         "file": "benchmarks/configs/tiny-llama-lora.json", "why": "test"})
    b["workloads"].append({"name": cell["name"], "config": cfg["name"], "chips": 1,
                           "traffic": cell["traffic_name"], "why": cell["why"]})
    b["per_layer"].append({k: m[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
                          | {"workloads": [cell["name"]]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return cell["name"]


@pytest.fixture(scope="module")
def with_the_family(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("family"))
    before = copy_of_the_benchmark(root)
    bench_before = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    return root, add_the_family(root), before, bench_before


def test_add_a_whole_family_as_files(with_the_family):
    """``--plumbing`` on the CPU through the copy's own run.py: the family is
    found by name, its weights (a bfloat16 base, and adapters in the type the
    program draws them in, the base's, whose ``b`` starts from the seed) are
    handed over as ``(trainable, frozen)``, its
    plain float32 reference follows the first rounds, and ``correct`` is
    true with no file of the benchmark edited."""
    root, cell, before, bench_before = with_the_family
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "1", "--trace", "1", "--plumbing"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root,
        timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["compared"]["frozen_leaves_off_stated_dtype"] == {"value": 0.0, "limit": 0, "ok": True}
    assert r["compared"]["dparam_worst"]["value"] < 0.05 and r["compared"]["loss_r0"]["value"] < 1e-3
    # the scope-reading metric finds no device trace on a CPU and is left out;
    # the general readers report as for any cell
    assert "lora.merge_ms_per_round" not in r["metrics"] and "engine.fused_round_pct" in r["metrics"]
    # which leaves the comparison left out is printed, and it is not a whole
    # adapter factor: both have a gradient in the first step
    line = next(ln for ln in p.stderr.splitlines() if "left out" in ln)
    detail = json.load(open(os.path.join(root, "bench_out", cell, "run-2147483659-t1.json")))
    left = detail["notes"]["leaves_left_out"]
    assert str(left) in line
    for factor in (".a", ".b"):
        assert sum(1 for k in left if k.endswith(factor)) < 15  # 15 matrices carry an adapter
    unchanged(root, before, bench_before)
    # every new file is new: nothing under benchmarks/ was there before
    added = [os.path.join(d, f) for d, _, fs in os.walk(os.path.join(root, "benchmarks"))
             for f in fs if "__pycache__" not in d and os.path.join(d, f) not in before]
    assert {os.path.relpath(a, root) for a in added} >= {
        "benchmarks/families/decoder_lora/__init__.py", "benchmarks/configs/tiny-llama-lora.json",
        "benchmarks/workloads/tiny-llama-lora.lm-s16.json",
        "benchmarks/metrics/lora.merge_ms_per_round.json", "benchmarks/readers/lora.py"}


@pytest.mark.parametrize("fault,caught_by", [
    ("frozen_base_in_float32", "frozen_leaves_off_stated_dtype"),
    ("adapter_not_applied", "dparam_worst")])
def test_a_fault_under_the_new_family_is_not_correct(with_the_family, fault, caught_by):
    root, cell, _, _ = with_the_family
    code = (
        "import json, sys; sys.path.insert(0, '.');"
        "from benchmarks import harness; import faults;"
        "harness.place_compile_cache();"
        f"r = harness.run_cell({cell!r}, 2147483659, 1.0, False, plumbing=True,"
        f" out_dir='out_{fault}', prepare=faults.{fault});"
        "print(json.dumps(r))")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is False
    assert [k for k, v in r["compared"].items() if not v["ok"]] == [caught_by]


def family_from_the_test_data():
    spec = importlib.util.spec_from_file_location(
        "decoder_lora_test_data", os.path.join(DATA, "family", "__init__.py"),
        submodule_search_locations=[os.path.join(DATA, "family")])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_the_new_family_counts_a_frozen_base_by_the_rule():
    """By hand at tiny-llama's sizes (hidden 128, 4 heads and 2 key-value
    heads of 32, MLP 384, 2 layers, 8192 rows, rank 4, sequence 16): frozen
    matrices 2 x 196608 a layer and 2 x 128 x 8192 for the head, twice each
    (no weight-gradient product); adapters 2 r (fan_in + fan_out), three
    times; attention 4 x 16 x 128 a layer, three times."""
    fam = family_from_the_test_data()
    sizes = json.load(open(os.path.join(DATA, "config.json")))
    frozen = 2 * (2 * 196608) + 2 * 128 * 8192
    adapters = 2 * 8 * (256 + 192 + 192 + 256 + 512 + 512 + 512) + 8 * (128 + 8192)
    attention = 2 * 4 * 16 * 128
    assert fam.forward_flops_per_token(sizes, 16) == frozen + adapters + attention == 3005440
    assert fam.train_flops_per_token(sizes, 16, None) == 2 * frozen + 3 * (adapters + attention)
    assert fam.train_flops_per_token(sizes, 16, None) == 6132736 < 3 * (frozen + attention)
    assert fam.program(sizes) == {"model": "tiny-llama", "vocab_size": 8192, "num_labels": 2,
                                  "task": "causal_lm", "lora_rank": 4}


def test_the_new_metrics_reader_reads_its_scope_from_the_table():
    spec = importlib.util.spec_from_file_location("lora_reader", os.path.join(DATA, "reader.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    table = {"fed.forward": 40.0, "transpose(fed.forward)": 90.0, "fed.lora_merge": 1.5,
             "transpose(fed.lora_merge)": 2.25, "fed.optimizer": 0.5, "unscoped": 3.0}
    assert mod.merge_ms_per_round({"trace": {"scopes": table, "op_names": {}}}) == 3.75
    assert mod.merge_ms_per_round({"trace": None}) is None
    assert mod.merge_ms_per_round({"trace": {"scopes": {"unscoped": 1.0}}}) is None
    with pytest.raises(RuntimeError):
        mod.merge_ms_per_round({"trace": {"scopes": None, "scopes_error": "no 'tf_op' stat"}})
