"""The Gated DeltaNet and gated attention decoder in the benchmark: its
family, its configuration, its cell, its metrics and their reader are NEW
FILES plus entries in BENCHMARK.json (proved as
benchmarks/tests/test_extend.py proves it for its stand-in: in a temporary
copy from which they are first taken away); the configuration's file
against the catalog row's published keys; the family's weights handed over
leaf for leaf; the readers against a scope table; the family's own four
planted faults."""

import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmarks import compare, families, harness, traffic
from benchmarks.families import delta_moe as fam

CELL = "qwen3-next-80b-a3b.lora-r16-s8192"
CONFIG = "qwen3-next-80b-a3b"
NEW_METRICS = ("gdn.mixer_ms_per_round", "gdn.rule_ms_per_round", "attn.gated_ms_per_round",
               "kernel.gated_delta_rule.roofline_pct")
NEW_FILES = ([f"families/delta_moe/{f}.py" for f in ("__init__", "weights", "plain", "flops", "readings")]
             + [f"configs/{CONFIG}.json", f"workloads/{CELL}.json", "readers/delta_moe.py"]
             + [f"metrics/{m}.json" for m in NEW_METRICS])

# config.json of Qwen/Qwen3-Next-80B-A3B-Instruct as published
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_the_configuration_holds_every_published_key():
    sizes = harness.load_json("configs", CONFIG + ".json")
    for key, value in PUBLISHED.items():
        assert sizes[key] == value, key
    catalog = os.environ.get("MODEL_CATALOG", "")
    if os.path.exists(catalog):  # the row itself, where a catalog of published configs is given
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["config"] == PUBLISHED and sizes["source"] == row["source_url"]
    assert sizes["reduced"] == ["layers", "experts_held", "vocab_rows"]
    assert set(sizes["reduced_why"]) == set(sizes["reduced"])
    assert (sizes["layers"], sizes["experts_held"], sizes["vocab_rows"]) == (4, 64, 18992)
    # the floors: a whole period, at least 8 experts, at least an eighth of the vocabulary
    assert sizes["layers"] == sizes["full_attention_interval"]
    assert sizes["vocab_rows"] * 8 == sizes["vocab_size"]
    assert sizes["experts_held"] * 8 == sizes["num_experts"]
    assert sizes["published_counts"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert "96 chips" in sizes["deployment"] and "eight ways" in sizes["deployment"]
    assert {"mtp", "balance_term"} == set(sizes["left_out"])
    assert {"initializer_range", "A_log", "dt_bias", "conv", "router_order", "lora"} <= set(sizes["assumed"])
    assert sizes["training"]["param_dtype"] == sizes["training"]["compute_dtype"] == "bfloat16"
    assert sizes["lora"]["r"] == 16 and sizes["lora"]["dtype"] == "float32"
    assert sizes["plumbing"]["program_model"] == "tiny-delta-moe"
    # the family is found by the file's name and answers the whole interface
    assert families.of(sizes) is fam and all(callable(getattr(fam, f)) for f in families.INTERFACE)
    b = harness.load_benchmark()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == sizes["reduced"] and entry["source"] == sizes["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json" and len(entry["why"]) <= 200


def test_the_cell_is_the_named_traffic():
    cell = harness.load_json("workloads", CELL + ".json")
    t = cell["traffic"]
    assert t == {"clients": 2, "local_batches": 4, "batch": 1, "seq": 8192, "full_share": 0.5,
                 "min_len": 1024, "label_signal": 0.0}
    assert traffic.tokens_per_round(t) == 65536 and cell["traffic_name"] == "lora-r16-s8192"
    lengths = sorted(traffic.row_lengths(8, 8192, 0.5, 1024).tolist())
    assert lengths == [1024, 3413, 5802, 8191, 8192, 8192, 8192, 8192]
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
    assert entry == {"name": CELL, "config": CONFIG, "traffic": "lora-r16-s8192", "chips": 1,
                     "why": cell["why"]} and len(cell["why"]) <= 200
    listed = [m["name"] for m in b["per_layer"] if m.get("workloads") == [CELL]]
    assert listed == list(NEW_METRICS)
    for m in b["per_layer"]:  # no list that was there gained or lost a cell
        assert m["name"] in NEW_METRICS or CELL not in m.get("workloads", [])
    sizes = harness.load_json("configs", CONFIG + ".json")
    batches, _ = traffic.make(dict(t, clients=1, local_batches=1), sizes["vocab_rows"], 2, 7,
                              job="causal_lm")
    assert batches["ids"].max() < sizes["vocab_rows"] and set(batches) == {"ids", "mask", "example_mask"}


def test_the_weights_hand_over_leaf_for_leaf(tmp_path):
    """The family's trees are the program's own, leaf for leaf in shape and
    type (``harness.hand_over`` refuses anything else), the base in the stated
    bfloat16 and the adapters in float32; ``from_program`` gives back the
    trained leaves in the family's naming."""
    cell, sizes = harness.load_cell(CELL, plumbing=True)
    run = harness.Run(cell, sizes, 2147483711, 0.0, False, True, str(tmp_path), 0.0)
    engine = harness.setup_engine(run)  # hands over, or raises
    flat = fam.make_weights(sizes, 2147483711)
    trainable, frozen = fam.to_program(flat, sizes)
    for mine, own in ((trainable, engine.trainable0), (frozen, engine.frozen)):
        assert harness._leaves(mine) == harness._leaves(own)
    assert {str(x.dtype) for x in jax.tree.leaves(engine.frozen)} == {"bfloat16"}
    back = fam.from_program(jax.device_get(engine.trainable0), sizes)
    assert set(back) == {k for k in flat if k.endswith((".a", ".b"))}
    for k, v in back.items():
        np.testing.assert_array_equal(np.asarray(v), flat[k], err_msg=k)


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
    os.rmdir(os.path.join(B, "families", "delta_moe"))
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
    assert not any("delta_moe" in p or CONFIG in p for p in before)
    # the PR: new files, and BENCHMARK.json as it stands
    os.makedirs(os.path.join(B, "families", "delta_moe"))
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
    fwd = "jit(f)/jit(main)/vmap(jvp(fed.forward))/DeltaMoELM/layer_0/"
    bwd = "jit(f)/jit(main)/transpose(vmap(jvp(fed.forward)))/DeltaMoELM/layer_0/"
    names = {
        fwd + "fed.gdn/linear_attn/in_proj_qkvz/dot_general": 40.0,
        fwd + "fed.gdn/linear_attn/in_proj_qkvz/fed.lora/dot_general": 0.5,
        fwd + "fed.gdn/linear_attn/fed.gdn.conv/mul": 3.0,
        bwd + "fed.gdn/linear_attn/fed.gdn.conv/mul": 5.0,
        fwd + "fed.gdn/linear_attn/fed.gdn.rule/while/body/dot_general": 20.0,
        bwd + "fed.gdn/linear_attn/fed.gdn.rule/while/body/dot_general": 60.0,
        bwd + "fed.gdn/linear_attn/fed.gdn.rule/while/body/triangular_solve": 20.0,
        fwd + "fed.gdn/linear_attn/fed.gdn.gate_norm/mul": 1.5,
        fwd.replace("layer_0", "layer_3") + "fed.gattn/self_attn/q_proj/dot_general": 4.0,
        bwd.replace("layer_0", "layer_3") + "fed.gattn/self_attn/pallas_call": 6.0,
        fwd + "moe/fed.moe.experts/pallas_call": 100.0,
        "jit(f)/jit(main)/vmap(jvp(fed.forward))/DeltaMoELM/fed.lm_head/lm_head/dot_general": 2.0,
    }
    cell, sizes = harness.load_cell(CELL)
    from benchmarks import yardstick

    # 2 clients x 4 steps x 3 GDN layers x 1 row x 128 chunks a round, 4 rounds
    ctx = {"trace": {"scopes": {"x": 1.0}, "op_names": names}, "cell": cell, "sizes": sizes,
           "rounds": 4, "platform": "tpu", "device_kind": "TPU v5 lite", "yardstick": yardstick,
           "phases": {"round_program": {"children": {"records": {
               "count": 2, "gdn_chunks": 4 * 3072, "moe_slots_held": 7}}}}}
    assert _reader("gdn.mixer_ms_per_round")(ctx) == 150.0
    assert _reader("gdn.rule_ms_per_round")(ctx) == 100.0
    assert _reader("attn.gated_ms_per_round")(ctx) == 10.0
    from benchmarks.families.delta_moe import flops

    flop, byts = flops.gated_delta_work(sizes, 3072)
    want = 100 * max(flop / 197e12, byts / 819e9) / 100e-3
    assert abs(_reader("kernel.gated_delta_rule.roofline_pct")(ctx) - want) < 1e-9 and 0 < want < 100
    # another program (the parent, another model such as the state-space
    # cell's): nothing to read, no error
    other = dict(ctx, trace={"scopes": {"fed.forward": 1.0}, "op_names": {
        "jit(f)/fed.forward/SSMMoELM/layer_0/fed.ssm/fed.ssm.scan/dot_general": 1.0,
        "jit(f)/fed.forward/SSMMoELM/layer_5/fed.attn/dot_general": 1.0}},
        phases={"round_program": {"children": {"records": {"count": 2, "ssm_scan_chunks": 9}}}})
    for m in NEW_METRICS:
        assert _reader(m)(other) is None, m
        assert _reader(m)(dict(other, trace=None)) is None, m
    # the scopes without the counter, or off a TPU: no roofline share
    assert _reader("kernel.gated_delta_rule.roofline_pct")(dict(ctx, phases=other["phases"])) is None
    assert _reader("kernel.gated_delta_rule.roofline_pct")(dict(ctx, platform="cpu")) is None
    with pytest.raises(RuntimeError):
        _reader("gdn.rule_ms_per_round")(dict(ctx, trace={"scopes": None, "scopes_error": "no stat"}))


@pytest.mark.parametrize("name", sorted(fam.FAULTS))
def test_each_of_the_familys_own_faults_is_not_correct(name):
    """This family's own planted faults, in the reference put in the
    program's place (as benchmarks/calibrate.py plants its two): the rule's
    state not carried from chunk to chunk, the rule without its erasing term,
    held expert 1's part left out and the attention's output gate left out,
    each fail a limit of the plumbing cell; the sound reference in the stated
    precision passes every one."""
    cell, sizes = harness.load_cell(CELL, plumbing=True)
    program = fam.program(sizes)
    seed = 2147483801
    batches, n_ex = traffic.make(cell["traffic"], program["vocab_size"], 2, seed, job="causal_lm")
    masks = [[1.0, 1.0]] * cell["check"]["rounds"]
    recs = [{"mask": m, "auth": [1.0, 1.0], "train_loss": 0.0} for m in masks]
    sound = fam.reference(sizes, seed, batches, masks, n_ex)
    stated = fam.reference(sizes, seed, batches, masks, n_ex, precision=fam.precisions(sizes)[0])
    planted = fam.reference(sizes, seed, batches, masks, n_ex, fault=fam.FAULTS[name])

    def judged(r):
        v, _ = compare.numbers(r["losses"], sound["losses"], r["trained"], sound["trained"],
                               sound["start"], sound["grad_norms"], recs, True, len(recs) * 2, 2, 0,
                               stated=stated["trained"])
        return compare.judge(v, {k: x for k, x in cell["limits"].items() if k in v})

    assert judged(stated)[1] is True
    rows, ok = judged(planted)
    assert ok is False and "turn_vs_stated" in [n for n, _, _, good in rows if not good]
    assert np.isfinite(planted["losses"]).all()
