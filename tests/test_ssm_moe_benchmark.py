"""The state-space and attention hybrid decoder in the benchmark: its family,
its configuration, its cell, its metrics and their reader are NEW FILES plus
entries in BENCHMARK.json (proved as benchmarks/tests/test_extend.py proves
it for its stand-in: in a temporary copy from which they are first taken
away); the configuration's file against the catalog row's published keys;
the readers against a scope table; the family's own two planted faults."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import compare, harness, traffic
from benchmarks.families import ssm_moe as fam

CELL = "granite-4.0-h-small.lora-r16-s4096"
CONFIG = "granite-4.0-h-small"
NEW_METRICS = ("ssm.mixer_ms_per_round", "ssm.scan_ms_per_round", "ssm.conv_ms_per_round",
               "attn.nope_ms_per_round", "kernel.ssm_scan.roofline_pct")
NEW_FILES = ([f"families/ssm_moe/{f}.py" for f in ("__init__", "weights", "plain", "flops", "readings")]
             + [f"configs/{CONFIG}.json", f"workloads/{CELL}.json", "readers/ssm_moe.py"]
             + [f"metrics/{m}.json" for m in NEW_METRICS])

# config.json of ibm-granite/granite-4.0-h-small as the catalog beside the
# model-configs guide holds it
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 768,
    "layer_types": ["attention" if i % 10 == 5 else "mamba" for i in range(40)],
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 128, "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True, "vocab_size": 100352}


def test_the_configuration_holds_every_published_key():
    sizes = harness.load_json("configs", CONFIG + ".json")
    for key, value in PUBLISHED.items():
        assert sizes[key] == value, key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the row itself, where the guide is installed
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == CONFIG)
        assert row["config"] == PUBLISHED and sizes["source"] == row["source_url"]
    assert sizes["reduced"] == ["layers", "experts_held", "vocab_rows"]
    assert set(sizes["reduced_why"]) == set(sizes["reduced"])
    assert (sizes["layers"], sizes["experts_held"], sizes["vocab_rows"]) == (10, 18, 25088)
    # the floors: a whole period, at least 8 experts, at least an eighth of the vocabulary
    assert sizes["layer_types"][:10].count("attention") == 1 and sizes["layers"] == 10
    assert sizes["vocab_rows"] * 4 == sizes["vocab_size"]
    assert sizes["experts_held"] * 4 == sizes["num_local_experts"]
    assert sizes["published_counts"] == {
        "num_hidden_layers": 40, "num_local_experts": 72, "vocab_size": 100352}
    assert "16 chips" in sizes["deployment"] and "four ways" in sizes["deployment"]
    assert {"time_step_limit", "head_dim", "initializer_range", "recurrence", "router_order",
            "lora"} <= set(sizes["assumed"])
    assert sizes["training"]["param_dtype"] == sizes["training"]["compute_dtype"] == "bfloat16"
    assert sizes["lora"]["r"] == 16 and sizes["lora"]["dtype"] == "float32"
    assert sizes["plumbing"]["program_model"] == "tiny-ssm-moe"
    b = harness.load_benchmark()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == sizes["reduced"] and entry["source"] == sizes["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"


def test_the_cell_is_the_traffic_the_issue_names():
    cell = harness.load_json("workloads", CELL + ".json")
    t = cell["traffic"]
    assert t == {"clients": 2, "local_batches": 4, "batch": 1, "seq": 4096, "full_share": 0.5,
                 "min_len": 512, "label_signal": 0.0}
    assert traffic.tokens_per_round(t) == 32768 and cell["traffic_name"] == "lora-r16-s4096"
    lengths = sorted(traffic.row_lengths(8, 4096, 0.5, 512).tolist())
    assert lengths == [512, 1706, 2901, 4095, 4096, 4096, 4096, 4096]
    assert cell["fed"] == {
        "mode": "server", "eval_every": 0, "donate": True, "dataset": "synthetic",
        "partition": {"kind": "iid", "iid_samples": 4}, "ledger": {"enabled": True},
        "rounds_per_dispatch": 2}
    assert cell["check"] == {"rounds": 2}
    assert cell["trace"] == {"skip_dispatches": 1, "dispatches": 1} and cell["chips"] == 1
    for exact in ("frozen_leaves_off_stated_dtype", "compiles_in_window", "chain_broken",
                  "auth_failed_rounds", "nonfinite_rounds", "chain_missing_entries",
                  "mask_mismatch_rounds"):
        assert cell["limits"][exact] == 0
    assert {"dparam_worst", "turn_vs_stated"} <= set(cell["limits"])
    b = harness.load_benchmark()
    entry = next(w for w in b["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": CONFIG, "traffic": "lora-r16-s4096", "chips": 1,
                     "why": cell["why"]} and len(cell["why"]) <= 200
    listed = [m["name"] for m in b["per_layer"] if m.get("workloads") == [CELL]]
    # the family's metrics stand together, in order (a later PR's entries follow them)
    first = next(i for i, m in enumerate(b["per_layer"]) if m["name"] in NEW_METRICS)
    assert listed == list(NEW_METRICS) and b["per_layer"][first:first + len(listed)] == [
        m for m in b["per_layer"] if m["name"] in NEW_METRICS]
    for m in b["per_layer"]:  # no list that was there gained or lost a cell
        assert m["name"] in NEW_METRICS or CELL not in m.get("workloads", [])
    sizes = harness.load_json("configs", CONFIG + ".json")
    batches, _ = traffic.make(dict(t, clients=1, local_batches=1), sizes["vocab_rows"], 2, 7,
                              job="causal_lm")
    assert batches["ids"].max() < sizes["vocab_rows"] and set(batches) == {"ids", "mask", "example_mask"}


def test_the_family_is_new_files_only(tmp_path):
    """The parent's ``benchmarks/`` (this tree's, with this family's files and
    entries taken away) plus the new files and BENCHMARK.json as it stands,
    nothing else edited: the cell's plumbing runs through the copy's own
    run.py and is correct."""
    root = str(tmp_path)
    B = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(harness.ROOT, "benchmarks"), B,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(harness.ROOT, "bcfl_tpu"), os.path.join(root, "bcfl_tpu"))
    held_back = {}
    for f in NEW_FILES:
        with open(os.path.join(B, f), "rb") as fh:
            held_back[f] = fh.read()
        os.remove(os.path.join(B, f))
    os.rmdir(os.path.join(B, "families", "ssm_moe"))
    after = harness.load_benchmark()
    before_b = {
        k: ([e for e in v if e.get("name") not in (CONFIG, CELL, *NEW_METRICS)]
            if isinstance(v, list) and v and isinstance(v[0], dict) else v)
        for k, v in after.items()}
    assert sum(len(v) for v in after.values() if isinstance(v, list)) \
        - sum(len(v) for v in before_b.values() if isinstance(v, list)) == 2 + len(NEW_METRICS)
    before = {}
    for d, _, files in os.walk(B):
        for f in files:
            before[os.path.join(d, f)] = open(os.path.join(d, f), "rb").read()
    assert not any("ssm_moe" in p or CONFIG in p for p in before)
    # the PR: new files, and BENCHMARK.json as it stands
    os.makedirs(os.path.join(B, "families", "ssm_moe"))
    for f, content in held_back.items():
        assert not os.path.exists(os.path.join(B, f))
        with open(os.path.join(B, f), "wb") as fh:
            fh.write(content)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(after, fh)
    p = subprocess.run(
        [sys.executable, os.path.join(B, "run.py"), "--workload", CELL, "--seed", "2147483777",
         "--seconds", "1", "--trace", "1", "--plumbing"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root,
        timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["compared"]["frozen_leaves_off_stated_dtype"] == {"value": 0.0, "limit": 0, "ok": True}
    assert r["compared"]["compiles_in_window"]["value"] == 0.0
    # the device's metrics wait for a trace; those without a list report on a CPU too
    assert not set(NEW_METRICS) & set(r["metrics"]) and "engine.fused_round_pct" in r["metrics"]
    for path, content in before.items():
        assert open(path, "rb").read() == content, path
    # what was there before the family's entries still stands before them (a
    # later PR's entries follow them)
    own = (CONFIG, CELL, *NEW_METRICS)
    for key, value in before_b.items():
        if not isinstance(value, list):
            assert after[key] == value
            continue
        first = next((i for i, e in enumerate(after[key])
                      if isinstance(e, dict) and e.get("name") in own), len(after[key]))
        assert after[key][:first] == value[:first], key


def _reader(name):
    spec = harness.load_json("metrics", name + ".json")
    return harness.load_reader(spec["reader"])


def test_the_readers_read_their_scopes_and_the_counter():
    fwd = "jit(f)/jit(main)/vmap(jvp(fed.forward))/SSMMoELM/layer_0/"
    bwd = "jit(f)/jit(main)/transpose(vmap(jvp(fed.forward)))/SSMMoELM/layer_0/"
    names = {
        fwd + "fed.ssm/mamba/in_proj/dot_general": 40.0,
        fwd + "fed.ssm/mamba/in_proj/fed.lora/dot_general": 0.5,
        fwd + "fed.ssm/mamba/fed.ssm.conv/mul": 3.0,
        bwd + "fed.ssm/mamba/fed.ssm.conv/mul": 5.0,
        fwd + "fed.ssm/mamba/fed.ssm.scan/while/body/dot_general": 20.0,
        bwd + "fed.ssm/mamba/fed.ssm.scan/while/body/dot_general": 60.0,
        bwd + "fed.ssm/mamba/fed.ssm.scan/while/body/exp": 20.0,
        fwd + "fed.ssm/mamba/fed.ssm.gate_norm/mul": 1.5,
        fwd.replace("layer_0", "layer_5") + "fed.attn/attention/q_proj/dot_general": 4.0,
        bwd.replace("layer_0", "layer_5") + "fed.attn/attention/pallas_call": 6.0,
        fwd + "moe/fed.moe.experts/pallas_call": 100.0,
        "jit(f)/jit(main)/vmap(jvp(fed.forward))/SSMMoELM/fed.lm_head/lm_head/dot_general": 2.0,
    }
    cell, sizes = harness.load_cell(CELL)
    from benchmarks import yardstick

    # 2 clients x 4 steps x 9 mamba layers x 1 row x 16 chunks a round, 4 rounds
    ctx = {"trace": {"scopes": {"x": 1.0}, "op_names": names}, "cell": cell, "sizes": sizes,
           "rounds": 4, "platform": "tpu", "device_kind": "TPU v5 lite", "yardstick": yardstick,
           "phases": {"round_program": {"children": {"records": {
               "count": 2, "ssm_scan_chunks": 4 * 1152, "moe_slots_held": 7}}}}}
    assert _reader("ssm.mixer_ms_per_round")(ctx) == 150.0
    assert _reader("ssm.scan_ms_per_round")(ctx) == 100.0
    assert _reader("ssm.conv_ms_per_round")(ctx) == 8.0
    assert _reader("attn.nope_ms_per_round")(ctx) == 10.0
    from benchmarks.families.ssm_moe import flops

    flop, byts = flops.ssm_scan_work(sizes, 1152)
    want = 100 * max(flop / 197e12, byts / 819e9) / 100e-3
    assert abs(_reader("kernel.ssm_scan.roofline_pct")(ctx) - want) < 1e-9 and 0 < want < 100
    # another program (the parent, another model): nothing to read, no error
    other = dict(ctx, trace={"scopes": {"fed.forward": 1.0},
                             "op_names": {"jit(f)/fed.forward/dot_general": 1.0}},
                 phases={"round_program": {"children": {"records": {"count": 2}}}})
    for m in NEW_METRICS:
        assert _reader(m)(other) is None, m
        assert _reader(m)(dict(other, trace=None)) is None, m
    # the scopes without the counter, or off a TPU: no roofline share
    assert _reader("kernel.ssm_scan.roofline_pct")(dict(ctx, phases=other["phases"])) is None
    assert _reader("kernel.ssm_scan.roofline_pct")(dict(ctx, platform="cpu")) is None
    with pytest.raises(RuntimeError):
        _reader("ssm.scan_ms_per_round")(dict(ctx, trace={"scopes": None, "scopes_error": "no stat"}))


@pytest.mark.parametrize("fault,caught_by", [
    ({"no_carry": True}, "turn_vs_stated"), ({"drop_expert": 1}, "turn_vs_stated")],
    ids=["state-not-carried", "expert-left-out"])
def test_each_of_the_familys_own_faults_is_not_correct(fault, caught_by):
    """This family's own planted faults, in the reference put in the
    program's place (as benchmarks/calibrate.py plants its two): the
    recurrence's state not carried from chunk to chunk, and held expert 1's
    part left out, each fail a limit of the plumbing cell; the sound
    reference in the stated precision passes every one."""
    cell, sizes = harness.load_cell(CELL, plumbing=True)
    program = fam.program(sizes)
    seed = 2147483801
    batches, n_ex = traffic.make(cell["traffic"], program["vocab_size"], 2, seed, job="causal_lm")
    masks = [[1.0, 1.0]] * cell["check"]["rounds"]
    recs = [{"mask": m, "auth": [1.0, 1.0], "train_loss": 0.0} for m in masks]
    sound = fam.reference(sizes, seed, batches, masks, n_ex)
    stated = fam.reference(sizes, seed, batches, masks, n_ex, precision=fam.precisions(sizes)[0])
    planted = fam.reference(sizes, seed, batches, masks, n_ex, fault=fault)

    def judged(r):
        v, _ = compare.numbers(r["losses"], sound["losses"], r["trained"], sound["trained"],
                               sound["start"], sound["grad_norms"], recs, True, len(recs) * 2, 2, 0,
                               stated=stated["trained"])
        return compare.judge(v, {k: x for k, x in cell["limits"].items() if k in v})

    assert judged(stated)[1] is True
    rows, ok = judged(planted)
    assert ok is False and caught_by in [n for n, _, _, good in rows if not good]
    assert np.isfinite(planted["losses"]).all()
