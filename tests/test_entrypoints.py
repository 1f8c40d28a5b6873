"""Entrypoints: presets match the SURVEY.md §2.1 matrix; CLI smoke run; the
driver graft hooks compile and execute."""

import subprocess
import sys

import pytest

from bcfl_tpu.entrypoints import build_presets, get_preset, list_presets, run


def test_cli_lint_subcommand(capsys):
    """`bcfl-tpu lint` dispatches before the run argparse (like trace):
    --list-checkers prints the catalogue and exits 0, and the repo-wide
    default run is the ANALYSIS.md standing guard (exit 0 == zero
    unsuppressed findings)."""
    from bcfl_tpu.entrypoints.__main__ import main

    with pytest.raises(SystemExit) as exc:
        main(["lint", "--list-checkers"])
    assert exc.value.code == 0
    out = capsys.readouterr().out
    for cid in ("guarded-by", "lock-order", "determinism",
                "telemetry-schema", "socket-deadline", "no-frame-concat"):
        assert cid in out
    with pytest.raises(SystemExit) as exc:
        main(["lint"])  # default paths: the installed package
    assert exc.value.code == 0, capsys.readouterr().out


def test_preset_matrix():
    p = build_presets()
    assert len(p) >= 13
    # server_IID_IMDB.py row: biobert, 2 labels, 20 clients, 20 rounds, IID 100
    c = p["server_iid_imdb"]
    assert (c.mode, c.model, c.num_labels, c.num_clients, c.num_rounds) == (
        "server", "biobert-base", 2, 20, 20)
    assert c.partition.kind == "iid" and c.partition.iid_samples == 100
    # serverless_NonIID_IMDB.py row: albert, 300k/240 trailing, unweighted
    c = p["serverless_noniid_imdb"]
    assert c.mode == "serverless" and not c.weighted_agg
    assert (c.partition.stride, c.partition.train_span, c.partition.test_mode) == (
        300, 240, "trailing")
    # medical NonIID: 500i/400 fixed test slice
    c = p["serverless_noniid_medical"]
    assert (c.partition.stride, c.partition.train_span, c.partition.test_span,
            c.partition.test_mode) == (500, 400, 400, "fixed")
    # BC-FL preset wires ledger + pagerank + async together
    c = p["bcfl_async_pagerank"]
    assert c.ledger.enabled and c.sync == "async"
    assert c.topology.anomaly_filter == "pagerank"


def test_hf_variant_sets_checkpoint():
    c = get_preset("serverless_noniid_imdb", hf=True)
    assert c.hf_checkpoint == "albert-base-v2"
    assert c.tokenizer == "albert-base-v2"


def test_unknown_preset():
    with pytest.raises(KeyError):
        get_preset("nope")


def test_smoke_preset_runs():
    res = run(get_preset("smoke"), verbose=False)
    assert len(res.metrics.rounds) == 2
    assert res.metrics.rounds[-1].global_acc is not None


@pytest.mark.slow  # full engine/CLI run: deeper-tier budget
def test_cli_smoke():
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-m", "bcfl_tpu.entrypoints",
         "--preset", "smoke", "--rounds", "1"],
        capture_output=True, text=True, timeout=600, cwd=repo,
    )
    assert out.returncode == 0, out.stderr
    assert "global_accuracies" in out.stdout


@pytest.mark.slow  # full engine/CLI run: deeper-tier budget
def test_graft_entry_hooks():
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import __graft_entry__ as g

    import jax

    fn, args = g.entry()
    logits = jax.jit(fn)(*args)
    assert logits.shape == (8, 2)
    g.dryrun_multichip(len(jax.devices()))


def test_run_sweep_records_artifacts(tmp_path):
    """--sweep must emit the reference notebooks' figure set (latency /
    accuracy / memory by client count, cells 15/18/21) + a JSON record."""
    import json

    from bcfl_tpu.config import FedConfig, PartitionConfig
    from bcfl_tpu.entrypoints.run import run_sweep

    cfg = FedConfig(
        name="sweeptest", model="tiny-bert", dataset="synthetic",
        mode="serverless", num_clients=2, num_rounds=1, seq_len=16,
        batch_size=4, max_local_batches=1,
        partition=PartitionConfig(kind="iid", iid_samples=8))
    out = run_sweep(cfg, client_counts=[2, 4], verbose=False,
                    out_dir=str(tmp_path))
    assert sorted(out) == [2, 4]
    rec = json.loads((tmp_path / "sweeptest_sweep.json").read_text())
    assert rec["counts"] == [2, 4]
    assert all(rec["runs"][k]["final_acc"] is not None for k in ("2", "4"))
    figs = sorted(p.name for p in tmp_path.glob("*.png"))
    assert figs == ["sweeptest_sweep_accuracy.png",
                    "sweeptest_sweep_latency.png",
                    "sweeptest_sweep_memory.png"]


def test_cli_fused_tamper_demo(capsys):
    """--fused-tamper R:C:SCALE drives the in-graph transport-corruption
    demo end-to-end from the CLI: the corrupted client fails ledger auth in
    that round (and only there), everyone else passes."""
    import numpy as np

    from bcfl_tpu.entrypoints.__main__ import main as cli_main
    from bcfl_tpu.fed import engine as engine_mod

    recorded = {}
    orig_run = engine_mod.FedEngine.run

    def spy_run(self, *a, **kw):
        res = orig_run(self, *a, **kw)
        recorded["rounds"] = res.metrics.rounds
        return res

    engine_mod.FedEngine.run = spy_run
    try:
        cli_main(["--preset", "smoke", "--mode", "server", "--rounds", "2",
                  "--rounds-per-dispatch", "2", "--eval-every", "2",
                  "--ledger", "--fused-tamper", "1:0:1e6"])
    finally:
        engine_mod.FedEngine.run = orig_run
    rounds = recorded["rounds"]
    C = len(rounds[0].auth)
    assert rounds[0].auth == [1.0] * C
    assert rounds[1].auth == [0.0] + [1.0] * (C - 1)


def test_cli_fused_tamper_bad_spec():
    from bcfl_tpu.entrypoints.__main__ import main as cli_main

    with pytest.raises(SystemExit, match="ROUND:CLIENT:SCALE"):
        cli_main(["--preset", "smoke", "--ledger",
                  "--fused-tamper", "nonsense"])
    with pytest.raises(SystemExit, match="client out of range"):
        cli_main(["--preset", "smoke", "--clients", "2", "--ledger",
                  "--fused-tamper", "0:5:1.0"])


def test_cli_fused_tamper_requires_ledger_and_valid_round():
    from bcfl_tpu.entrypoints.__main__ import main as cli_main

    with pytest.raises(SystemExit, match="ledger"):
        cli_main(["--preset", "smoke", "--rounds-per-dispatch", "2",
                  "--fused-tamper", "0:0:1.0"])
    with pytest.raises(SystemExit, match="round out of range"):
        cli_main(["--preset", "smoke", "--rounds", "2", "--ledger",
                  "--fused-tamper", "2:0:1.0"])
