"""Adapter-only federated exchange (COMPRESSION.md "Adapter exchange").

Config-level: ``lora_ranks`` spec parsing + canonicalization (``lora_rank``
becomes the cohort max), the heterogeneous-rank composition rejections
(robust aggregators / gossip / faithful / registry / dist), and
the capability-table rows for adapter exchange.
Math-level: the static rank mask, per-client adapter clipping, the
rank-aware RBLA weighted mean (padded coordinates excluded per rank dim,
per-dim fallback when every contributor is padding), and the Shannon
effective-rank statistic.
Engine-level: a heterogeneous fleet trains under RBLA with the effective
rank recorded every round and ZERO per-round retraces; LoRA composes with
int8+topk error feedback bit-identically across crash/resume (adapter + EF
residual ride the checkpoint); resuming under a different rank layout is
refused loudly.
Dist-level (marker ``dist``): a real 2-peer loopback run ships ONLY
adapter-scale update frames, with ledger authentication over the adapter
payloads and robust merge votes on the flattened adapter vectors.

The whole file is fast/`not slow`, so tier-1 runs it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bcfl_tpu.compression import CompressionConfig, payload_nbytes
from bcfl_tpu.config import (
    DistConfig,
    FedConfig,
    LedgerConfig,
    PartitionConfig,
    capability_table,
    parse_lora_ranks,
)
from bcfl_tpu.faults import FaultPlan, SimulatedCrash
from bcfl_tpu.fed.engine import FedEngine
from bcfl_tpu.models import lora as lora_lib
from bcfl_tpu.parallel import gspmd

INT8_TOPK = CompressionConfig(kind="int8+topk", topk_frac=0.1)


def _tiny(**kw):
    base = dict(
        dataset="synthetic", model="tiny-bert", num_clients=4, num_rounds=2,
        seq_len=16, batch_size=4, max_local_batches=2, vocab_size=512,
        eval_every=0,
        partition=PartitionConfig(kind="iid", iid_samples=8),
    )
    base.update(kw)
    return FedConfig(**base)


# ------------------------------------------------------------------- config


def test_parse_lora_ranks():
    assert parse_lora_ranks("2,4,8") == (2, 4, 8)
    assert parse_lora_ranks("16") == (16,)
    for bad in ("", "2,x", "2,,4", "0", "2,-4"):
        with pytest.raises(ValueError, match="lora_ranks"):
            parse_lora_ranks(bad)


def test_lora_ranks_canonicalization():
    cfg = _tiny(lora_ranks="2,4")
    # lora_rank canonicalizes to the cohort max, so every existing
    # `lora_rank > 0` switch sees the padded ceiling
    assert cfg.lora_rank == 4
    assert cfg.lora_rank_spec == (2, 4)
    # the spec cycles over the stacked client axis
    assert cfg.client_lora_ranks == (2, 4, 2, 4)
    assert _tiny(lora_ranks="2,4,8").client_lora_ranks == (2, 4, 8, 2)
    # uniform fleets report no spec at all (shared program-cache entry)
    assert _tiny(lora_rank=4).client_lora_ranks is None
    assert _tiny().lora_rank_spec is None
    with pytest.raises(ValueError, match="not both"):
        _tiny(lora_ranks="2,4", lora_rank=2)


@pytest.mark.parametrize("kw,needle", [
    (dict(aggregator="trimmed_mean"), "structural zero padding"),
    (dict(aggregator="median"), "structural zero padding"),
    (dict(mode="serverless"), "mode='server'"),
    (dict(faithful=True), "faithful"),
    (dict(registry_size=100, sample_clients=4), "registry"),
])
def test_hetero_composition_rejections(kw, needle):
    with pytest.raises(ValueError, match=needle):
        _tiny(lora_ranks="2,4", **kw)
    # a UNIFORM spec ("4,4" = everyone at 4) is not heterogeneous: the
    # combination constructs wherever plain lora_rank=4 would
    if "registry" not in kw and "mode" not in kw and "faithful" not in kw:
        _tiny(lora_ranks="4,4", **kw)


def test_hetero_rejected_on_dist_via_caps_table():
    with pytest.raises(ValueError, match="not supported on runtime='dist'"):
        FedConfig(runtime="dist", sync="async", eval_every=0, num_clients=4,
                  lora_ranks="2,4", dist=DistConfig(peers=2))
    try:
        FedConfig(runtime="dist", sync="async", eval_every=0, num_clients=4,
                  lora_ranks="2,4", dist=DistConfig(peers=2))
    except ValueError as e:
        assert "uniform lora_rank" in str(e)
    # ... while UNIFORM adapter exchange is a declared dist capability
    cfg = FedConfig(runtime="dist", sync="async", eval_every=0,
                    num_clients=4, lora_rank=2, dist=DistConfig(peers=2))
    rows = {f: v for f, _, v in capability_table(cfg)}
    assert rows["LoRA adapter exchange"] is True


def test_uniform_lora_ranks_build_the_plain_programs():
    from bcfl_tpu.core.mesh import client_mesh
    from bcfl_tpu.fed.client_step import build_programs
    from bcfl_tpu.models import build

    model = build("tiny-bert", num_labels=2, vocab_size=512)
    # a uniform tuple normalizes onto the PLAIN program set: identical
    # object, so every cache hit keeps working
    a = build_programs(model, client_mesh(4))
    b = build_programs(model, client_mesh(4), lora_ranks=(4, 4, 4, 4))
    assert b is a


# --------------------------------------------------------------- rank math


def test_rank_mask_and_clip_adapters():
    m = lora_lib.rank_mask((2, 4, 1))
    np.testing.assert_array_equal(
        np.asarray(m),
        [[1, 1, 0, 0], [1, 1, 1, 1], [1, 0, 0, 0]])

    adapters = {"enc": {"a": jnp.ones((3, 4)), "b": jnp.ones((4, 5))},
                "head": {"full": jnp.ones((2,))}}
    out = lora_lib.clip_adapters(adapters, m[0])
    np.testing.assert_array_equal(
        np.asarray(out["enc"]["a"]),
        np.concatenate([np.ones((3, 2)), np.zeros((3, 2))], axis=1))
    np.testing.assert_array_equal(
        np.asarray(out["enc"]["b"]),
        np.concatenate([np.ones((2, 5)), np.zeros((2, 5))], axis=0))
    # head leaves are full-tensor (not rank-structured): untouched
    np.testing.assert_array_equal(np.asarray(out["head"]["full"]),
                                  np.ones((2,)))


def test_rank_aware_weighted_mean_excludes_padding():
    # client 0 at rank 1 (dim 1 is padding), client 1 at rank 2
    mask = lora_lib.rank_mask((1, 2))
    a = jnp.stack([jnp.full((3, 2), 2.0), jnp.full((3, 2), 6.0)])
    b = jnp.stack([jnp.full((2, 5), 2.0), jnp.full((2, 5), 6.0)])
    full = jnp.stack([jnp.full((4,), 2.0), jnp.full((4,), 6.0)])
    tree = {"m": {"a": a, "b": b}, "h": {"full": full}}
    w = jnp.asarray([1.0, 3.0])
    out = gspmd.rank_aware_weighted_mean(tree, w, mask)
    # dim 0: both contribute -> (1*2 + 3*6)/4 = 5; dim 1: only client 1
    np.testing.assert_allclose(np.asarray(out["m"]["a"][:, 0]), 5.0)
    np.testing.assert_allclose(np.asarray(out["m"]["a"][:, 1]), 6.0)
    np.testing.assert_allclose(np.asarray(out["m"]["b"][0]), 5.0)
    np.testing.assert_allclose(np.asarray(out["m"]["b"][1]), 6.0)
    # 'full' leaves (task heads) take the PLAIN weighted mean
    np.testing.assert_allclose(np.asarray(out["h"]["full"]), 5.0)

    # zero-weight round: every dim falls back
    fb = {"m": {"a": jnp.full((3, 2), 9.0), "b": jnp.full((2, 5), 9.0)},
          "h": {"full": jnp.full((4,), 9.0)}}
    out0 = gspmd.rank_aware_weighted_mean(
        tree, jnp.zeros((2,)), mask, fallback=fb)
    for leaf in jax.tree.leaves(out0):
        np.testing.assert_allclose(np.asarray(leaf), 9.0)

    # PARTIAL fallback: with only the rank-1 client weighted, dim 1 has no
    # live contributor -> that dim alone reverts to the fallback
    out1 = gspmd.rank_aware_weighted_mean(
        tree, jnp.asarray([1.0, 0.0]), mask, fallback=fb)
    np.testing.assert_allclose(np.asarray(out1["m"]["a"][:, 0]), 2.0)
    np.testing.assert_allclose(np.asarray(out1["m"]["a"][:, 1]), 9.0)
    np.testing.assert_allclose(np.asarray(out1["m"]["b"][0]), 2.0)
    np.testing.assert_allclose(np.asarray(out1["m"]["b"][1]), 9.0)


def test_effective_rank_statistic():
    # R orthogonal equal-energy factor pairs -> effective rank == R
    adapters = {"m": {"a": jnp.eye(4), "b": 2.0 * jnp.eye(4)}}
    np.testing.assert_allclose(
        float(lora_lib.effective_rank(adapters)), 4.0, rtol=1e-5)
    # all energy in ONE dim -> 1.0 (the collapse signature)
    one = {"m": {"a": jnp.eye(4) * jnp.asarray([1.0, 0, 0, 0]),
                 "b": jnp.eye(4)}}
    np.testing.assert_allclose(
        float(lora_lib.effective_rank(one)), 1.0, rtol=1e-5)
    # zero adapters (b starts at zero) and head-only trees report 0.0
    zero = {"m": {"a": jnp.eye(4), "b": jnp.zeros((4, 4))}}
    assert float(lora_lib.effective_rank(zero)) == 0.0
    assert float(lora_lib.effective_rank({"h": {"full": jnp.ones(3)}})) == 0.0


# ------------------------------------------------------------------- engine


def test_hetero_engine_records_effective_rank_zero_retraces():
    eng = FedEngine(_tiny(lora_ranks="2,4"))
    res = eng.run()
    assert len(res.metrics.rounds) == 2
    for rec in res.metrics.rounds:
        # the rank-collapse guard: recorded every round, in (0, n_dims]
        assert rec.effective_rank is not None
        assert 0.0 < rec.effective_rank
    # the padding mask is static -> the round program compiled exactly once
    assert eng.progs.server_round._cache_size() == 1
    # bytes accounting is adapter-sized: the wire carries the adapter tree,
    # not the merged full model
    rec = res.metrics.rounds[0]
    assert rec.bytes_on_wire == payload_nbytes(None, res.trainable) * 4
    assert rec.bytes_on_wire < payload_nbytes(None, res.params)


def test_lora_compress_ef_crash_resume_bit_identical(tmp_path):
    """LoRA x int8+topk x error feedback: the checkpoint carries the
    adapter tree AND the adapter-shaped EF residual, so crash + resume
    reproduces the uninterrupted compressed run bit-for-bit — the pinned
    composition for `--lora-rank` + `--compress` + EF."""
    kw = dict(lora_rank=2, compression=INT8_TOPK, num_rounds=3,
              checkpoint_every=1)
    ref = FedEngine(_tiny(**kw)).run()
    cfg = _tiny(checkpoint_dir=str(tmp_path),
                faults=FaultPlan(crash_at_round=1), **kw)
    with pytest.raises(SimulatedCrash):
        FedEngine(cfg).run()
    res = FedEngine(cfg).run(resume=True)
    for a, b in zip(jax.tree.leaves(ref.trainable),
                    jax.tree.leaves(res.trainable)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the compressed exchange shipped adapter-scale payloads
    assert (res.metrics.rounds[-1].bytes_on_wire
            < res.metrics.rounds[-1].bytes_raw)

    # the checkpoint records the rank layout: resuming under a different
    # one would reinterpret the adapter/EF trees — refused loudly (same
    # guard class as the wire-format and prng-impl resume checks)
    with pytest.raises(ValueError, match="rank layout"):
        FedEngine(cfg.replace(lora_rank=4)).run(resume=True)
    with pytest.raises(ValueError, match="rank layout"):
        FedEngine(cfg.replace(lora_rank=0, lora_ranks="2,4")).run(
            resume=True)


def test_cli_lora_ranks_flag_fails_fast_on_bad_combos():
    """`--lora-ranks` reaches FedConfig, whose validation fires at CONFIG
    time — the CLI exits with the clear message before any engine work."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    site_pkgs = [p for p in sys.path if p.endswith("site-packages")]
    env["PYTHONPATH"] = os.pathsep.join([repo] + site_pkgs)
    env["JAX_PLATFORMS"] = "cpu"

    def cli(*flags):
        return subprocess.run(
            [sys.executable, "-S", "-m", "bcfl_tpu.entrypoints",
             "--preset", "smoke", *flags],
            capture_output=True, text=True, timeout=120, env=env, cwd=repo)

    out = cli("--lora-ranks", "2,4", "--lora-rank", "2")
    assert out.returncode != 0
    assert "not both" in out.stderr + out.stdout
    out = cli("--lora-ranks", "2,x")
    assert out.returncode != 0
    assert "comma-separated positive ints" in out.stderr + out.stdout


# --------------------------------------------------------------------- dist


@pytest.mark.dist
def test_dist_loopback_lora_adapter_exchange(tmp_path):
    """Adapters on the real wire: a 3-peer loopback federation with
    lora_rank=2 completes with every update frame at adapter scale (the
    ~12 MB full-model frame never crosses the socket), ledger replicas
    authenticating the adapter payloads on every peer, robust merge votes
    (trimmed mean needs a >= 3-deep buffer, hence 3 peers) over the
    flattened adapter vectors, and zero telemetry-invariant violations."""
    from bcfl_tpu.dist.harness import run_dist
    from bcfl_tpu.telemetry import collate_run

    peers = (0, 1, 2)
    cfg = FedConfig(
        name="dist_lora_smoke", runtime="dist", mode="server", sync="async",
        model="tiny-bert", dataset="synthetic", num_clients=6, num_rounds=3,
        seq_len=16, batch_size=4, max_local_batches=2, eval_every=0,
        lora_rank=2, aggregator="trimmed_mean",
        partition=PartitionConfig(kind="iid", iid_samples=8),
        ledger=LedgerConfig(enabled=True),
        dist=DistConfig(peers=3, buffer=3, buffer_timeout_s=5.0,
                        idle_timeout_s=120.0, peer_deadline_s=220.0,
                        checkpoint_every_versions=0))
    result = run_dist(cfg, str(tmp_path / "run"), deadline_s=240.0,
                      platform="cpu")
    assert result["returncodes"] == {"0": 0, "1": 0, "2": 0}, \
        result["log_tails"]
    assert result["ok"], result["log_tails"]
    reports = result["reports"]
    assert all(reports[p]["final_version"] >= cfg.num_rounds for p in peers)
    # ledger auth over adapter payloads: every chain replica verifies and
    # all replicas agree
    assert all(reports[p]["chain_ok"] for p in peers)
    assert len({reports[p]["chain_head"] for p in peers}) == 1

    col = collate_run(result["run_dir"])
    assert col["ok"], col["violations"]
    frames = [e["bytes"] for e in col["ordered"]
              if e["ev"] == "send" and e.get("ok")
              and e.get("type") == "update"]
    assert frames, "no update frames observed"
    # adapter-scale: rank-2 tiny-bert updates measure ~210 KB for a
    # 2-client slice vs ~12 MB full-model (scripts/lora_comm.py records
    # the measured ratio); 1 MB is an order-of-magnitude-safe ceiling
    assert max(frames) < 1_000_000, max(frames)
