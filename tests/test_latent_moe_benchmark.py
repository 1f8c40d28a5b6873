"""The latent-attention expert decoder in the benchmark: its family, its
configuration, its cell, its metrics and their reader are NEW FILES plus
entries in BENCHMARK.json (proved as benchmarks/tests/test_extend.py proves
it for its stand-in: in a temporary copy from which they are first taken
away); the configuration's file against the published keys; the readers
against a scope table; the family's own planted fault."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import compare, harness, traffic
from benchmarks.families import latent_moe as fam

CELL = "mistral-small-4.lora-r16-s2048"
CONFIG = "mistral-small-4"
NEW_METRICS = (
    "moe.experts_ms_per_round", "moe.route_ms_per_round", "attn.mla_ms_per_round",
    "lora.ms_per_round", "lm_head.ms_per_round", "moe.held_share_pct", "moe.rows_max_over_mean",
    "kernel.moe_grouped_matmul.roofline_pct", "kernel.flash_attention.roofline_pct")
NEW_FILES = ([f"families/latent_moe/{f}.py" for f in ("__init__", "weights", "plain", "flops", "readings")]
             + [f"configs/{CONFIG}.json", f"workloads/{CELL}.json", "readers/latent_moe.py"]
             + [f"metrics/{m}.json" for m in NEW_METRICS])

# config.json of mistralai/Mistral-Small-4-119B-2603 as the catalog beside the
# model-configs guide holds it (numbers and flags at the top level)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 12288, "kv_lora_rank": 256,
    "max_position_embeddings": 1048576, "mlp_bias": False, "model_type": "mistral4",
    "moe_intermediate_size": 2048, "n_group": 1, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 36, "num_key_value_heads": 32, "q_lora_rank": 1024, "qk_head_dim": 128,
    "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "routed_scaling_factor": 1, "sliding_window": None, "tie_word_embeddings": False,
    "topk_group": 1, "v_head_dim": 128, "vocab_size": 131072,
    "rope_parameters": {"beta_fast": 32, "beta_slow": 1, "factor": 128, "llama_4_scaling_beta": 0.1,
                        "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 8192,
                        "rope_theta": 10000, "rope_type": "yarn", "type": "yarn"}}


def test_the_configuration_holds_every_published_key():
    sizes = harness.load_json("configs", CONFIG + ".json")
    for key, value in PUBLISHED.items():
        assert sizes[key] == value, key
    assert sizes["reduced"] == ["layers", "experts_held", "vocab_rows", "vision_tower"]
    assert set(sizes["reduced_why"]) == set(sizes["reduced"])
    assert (sizes["layers"], sizes["experts_held"], sizes["vocab_rows"]) == (8, 16, 16384)
    assert sizes["vocab_rows"] * 8 == sizes["vocab_size"] and sizes["layers"] >= 4
    assert sizes["experts_held"] * 8 == sizes["n_routed_experts"]
    assert "8 chips share each layer" in sizes["deployment"]
    assert {"scoring_func", "llama_4_scaling", "initializer_range", "weights", "lora"} <= set(sizes["assumed"])
    assert sizes["training"]["param_dtype"] == sizes["training"]["compute_dtype"] == "bfloat16"
    assert sizes["lora"]["r"] == 16 and sizes["lora"]["dtype"] == "float32"
    b = harness.load_benchmark()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == sizes["reduced"] and entry["source"] == sizes["source"]


def test_the_cell_is_the_traffic_the_issue_names():
    cell = harness.load_json("workloads", CELL + ".json")
    t = cell["traffic"]
    assert (t["clients"], t["local_batches"], t["batch"], t["seq"]) == (2, 4, 2, 2048)
    assert (t["full_share"], t["min_len"]) == (0.5, 256) and traffic.tokens_per_round(t) == 32768
    assert cell["fed"]["rounds_per_dispatch"] == 2 and cell["check"]["rounds"] == 2
    assert cell["fed"]["ledger"] == {"enabled": True} and cell["fed"]["donate"] is True
    assert cell["trace"] == {"skip_dispatches": 1, "dispatches": 1} and cell["chips"] == 1
    for exact in ("frozen_leaves_off_stated_dtype", "compiles_in_window", "chain_broken",
                  "auth_failed_rounds", "nonfinite_rounds", "chain_missing_entries"):
        assert cell["limits"][exact] == 0
    b = harness.load_benchmark()
    listed = [m["name"] for m in b["per_layer"] if m.get("workloads") == [CELL]]
    assert listed == list(NEW_METRICS)
    # ids below the rows held, as the family maps them
    sizes = harness.load_json("configs", CONFIG + ".json")
    batches, n_ex = traffic.make(dict(t, clients=1, local_batches=1), 16384, 2, 7, job="causal_lm")
    assert batches["ids"].max() < sizes["vocab_rows"] and set(batches) == {"ids", "mask", "example_mask"}


def test_the_family_is_new_files_only(tmp_path):
    """The benchmark as it was before this family (everything but its files
    and its entries), then the files and entries added, nothing else edited:
    the cell runs through the copy's own run.py and is correct."""
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
    os.rmdir(os.path.join(B, "families", "latent_moe"))
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
    assert not any("latent_moe" in p or CONFIG in p for p in before)
    # the PR: new files, and BENCHMARK.json as it stands
    os.makedirs(os.path.join(B, "families", "latent_moe"))
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
    # the counters' metrics report on a CPU too; the device's wait for a trace
    assert 0 < r["metrics"]["moe.held_share_pct"]["value"] < 100
    assert r["metrics"]["moe.rows_max_over_mean"]["value"] >= 1.0
    assert "moe.experts_ms_per_round" not in r["metrics"] and "engine.fused_round_pct" in r["metrics"]
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


def test_the_readers_read_their_scopes_and_counters():
    names = {
        "jit(f)/jit(main)/vmap(jvp(fed.forward))/LatentMoELM/layer_0/fed.mla/attention/q_a_proj/dot_general": 4.0,
        "jit(f)/jit(main)/vmap(jvp(fed.forward))/LatentMoELM/layer_0/fed.mla/attention/q_a_proj/fed.lora/dot_general": 0.5,
        "jit(f)/jit(main)/vmap(jvp(fed.forward))/LatentMoELM/layer_0/fed.mla/attention/pallas_call": 3.0,
        "jit(f)/jit(main)/transpose(vmap(jvp(fed.forward)))/LatentMoELM/layer_0/fed.mla/attention/pallas_call": 6.0,
        "jit(f)/jit(main)/vmap(jvp(fed.forward))/LatentMoELM/layer_0/moe/fed.moe.route/top_k": 1.0,
        "jit(f)/jit(main)/vmap(jvp(fed.forward))/LatentMoELM/layer_0/moe/fed.moe.experts/pallas_call": 100.0,
        "jit(f)/jit(main)/vmap(jvp(fed.forward))/LatentMoELM/layer_0/moe/fed.moe.experts/mul": 0.25,
        "jit(f)/jit(main)/transpose(vmap(jvp(fed.forward)))/LatentMoELM/layer_0/moe/fed.moe.experts/pallas_call": 100.0,
        "jit(f)/jit(main)/vmap(jvp(fed.forward))/LatentMoELM/fed.lm_head/lm_head/dot_general": 2.0,
        "jit(f)/jit(main)/fed.optimizer/add": 0.125,
    }
    cell, sizes = harness.load_cell(CELL)
    from benchmarks import yardstick

    ctx = {"trace": {"scopes": {"x": 1.0}, "op_names": names}, "cell": cell, "sizes": sizes,
           "rounds": 4, "platform": "tpu", "device_kind": "TPU v5 lite", "yardstick": yardstick,
           "phases": {"round_program": {"children": {"records": {
               "count": 2, "moe_slots_held": 4 * 262144, "moe_slots_absent": 4 * 1835008,
               "moe_rows_max": 4 * 400}}}}}
    assert _reader("attn.mla_ms_per_round")(ctx) == 13.5
    assert _reader("lora.ms_per_round")(ctx) == 0.5
    assert _reader("moe.route_ms_per_round")(ctx) == 1.0
    assert _reader("moe.experts_ms_per_round")(ctx) == 200.25
    assert _reader("lm_head.ms_per_round")(ctx) == 2.0
    assert _reader("moe.held_share_pct")(ctx) == 12.5
    # 262144 held a round over 2 clients x 4 steps x 8 layers x 16 experts = 256 rows
    assert _reader("moe.rows_max_over_mean")(ctx) == 400 / 256
    from benchmarks.families.latent_moe import flops

    flop, byts = flops.grouped_matmul_work(sizes, 262144, 4 * 8)
    want = 100 * max(flop / 197e12, byts / 819e9) / 200e-3
    assert abs(_reader("kernel.moe_grouped_matmul.roofline_pct")(ctx) - want) < 1e-9 and want < 100
    flop, byts = flops.flash_attention_work(sizes, 2048, 2)
    want = 100 * 64 * max(flop / 197e12, byts / 819e9) / 9e-3
    assert abs(_reader("kernel.flash_attention.roofline_pct")(ctx) - want) < 1e-9
    # another program (the parent, another model): nothing to read, no error
    other = dict(ctx, trace={"scopes": {"fed.forward": 1.0},
                             "op_names": {"jit(f)/fed.forward/dot_general": 1.0}},
                 phases={"round_program": {"children": {"records": {"count": 2}}}})
    for m in NEW_METRICS:
        assert _reader(m)(other) is None, m
        assert _reader(m)(dict(other, trace=None)) is None, m
    with pytest.raises(RuntimeError):
        _reader("attn.mla_ms_per_round")(dict(ctx, trace={"scopes": None, "scopes_error": "no stat"}))


def test_an_experts_part_left_out_is_not_correct():
    """This family's own planted fault, in the reference put in the program's
    place (as benchmarks/calibrate.py plants its two): held expert 1's part
    left out fails a limit of the plumbing cell; the sound reference put in
    the program's place passes every one."""
    cell, sizes = harness.load_cell(CELL, plumbing=True)
    program = fam.program(sizes)
    seed = 2147483777
    batches, n_ex = traffic.make(cell["traffic"], program["vocab_size"], 2, seed, job="causal_lm")
    masks = [[1.0, 1.0]] * cell["check"]["rounds"]
    recs = [{"mask": m, "auth": [1.0, 1.0], "train_loss": 0.0} for m in masks]
    sound = fam.reference(sizes, seed, batches, masks, n_ex)
    stated = fam.reference(sizes, seed, batches, masks, n_ex, precision=fam.precisions(sizes)[0])
    fault = fam.reference(sizes, seed, batches, masks, n_ex, fault={"drop_expert": 1})

    def judged(r):
        v, _ = compare.numbers(r["losses"], sound["losses"], r["trained"], sound["trained"],
                               sound["start"], sound["grad_norms"], recs, True, len(recs) * 2, 2, 0,
                               stated=stated["trained"])
        return compare.judge(v, {k: x for k, x in cell["limits"].items() if k in v})

    assert judged(stated)[1] is True
    rows, ok = judged(fault)
    assert ok is False and [n for n, _, _, good in rows if not good] == ["turn_vs_stated"]
    assert np.isfinite(fault["losses"]).all()
