"""Chaos matrix: the fault-injection harness (bcfl_tpu.faults) against the
engine's resilience contracts (ROBUSTNESS.md).

For each fault class {dropout, straggler, corruption, crash-resume} a short
synthetic job must (a) complete with a finite (no NaN/Inf) global model,
(b) exclude corrupted clients from the aggregate via ledger auth, and
(c) resume bit-for-bit after a mid-run crash. Plus: the Byzantine-robust
aggregators compile into the round program once (no per-round retraces) and
recover clean-run accuracy under <= 1-in-4 corrupted clients; and the
crash-safe checkpoint layer falls back to the newest VALID checkpoint when
the newest one is truncated or corrupted.

Run standalone via ``scripts/chaos_smoke.sh`` (the `faults` marker); the
whole file is fast/`not slow`, so tier-1 exercises it too.
"""

import glob
import os

import numpy as np
import pytest

import jax

from bcfl_tpu.checkpoint import restore_latest, save_checkpoint
from bcfl_tpu.config import FedConfig, LedgerConfig, PartitionConfig
from bcfl_tpu.faults import FaultInjector, FaultPlan, SimulatedCrash
from bcfl_tpu.fed.engine import FedEngine

pytestmark = pytest.mark.faults


def _cfg(**kw):
    base = dict(
        dataset="synthetic", num_labels=2, seq_len=32, batch_size=16,
        vocab_size=512, model="tiny-bert", num_clients=4, num_rounds=2,
        learning_rate=3e-4, max_local_batches=4,
        partition=PartitionConfig(kind="iid", iid_samples=64),
    )
    base.update(kw)
    return FedConfig(**base)


def _tiny(**kw):
    """Smallest config that still exercises the full round machinery —
    for structural assertions where accuracy doesn't matter."""
    base = dict(
        dataset="synthetic", model="tiny-bert", num_clients=4, num_rounds=3,
        seq_len=16, batch_size=4, max_local_batches=2,
        partition=PartitionConfig(kind="iid", iid_samples=8),
    )
    base.update(kw)
    return FedConfig(**base)


def _leaves(tree):
    return jax.tree.leaves(jax.device_get(tree))


def _assert_finite(tree):
    for x in _leaves(tree):
        assert np.isfinite(np.asarray(x)).all(), "NaN/Inf in global model"


# --------------------------------------------------------------------- plan


def test_fault_plan_deterministic_and_seeded():
    plan = FaultPlan(seed=7, dropout_prob=0.5, straggler_prob=0.5,
                     corrupt_prob=0.5)
    for rnd in range(5):
        a = plan.dropout_keep(rnd, 16)
        b = plan.dropout_keep(rnd, 16)
        np.testing.assert_array_equal(a, b)  # same plan -> same schedule
    # lanes are independent: the dropout draw differs from the corrupt draw
    keep = plan.dropout_keep(0, 1000)
    row = plan.transport_scales(0, 1000)
    assert not np.array_equal(keep == 0.0, row > 0)
    # a different seed is a different schedule
    other = FaultPlan(seed=8, dropout_prob=0.5)
    assert any(
        not np.array_equal(plan.dropout_keep(r, 64), other.dropout_keep(r, 64))
        for r in range(4))


def test_fault_plan_noop_default_and_validation():
    plan = FaultPlan()
    assert not plan.enabled
    assert plan.dropout_keep(0, 4) is None
    assert plan.straggler_delays(0, 4) is None
    assert plan.transport_scales(0, 4) is None
    assert not plan.should_crash(0)
    with pytest.raises(ValueError, match="dropout_prob"):
        FaultPlan(dropout_prob=1.5)
    with pytest.raises(ValueError, match="tuple"):
        FaultPlan(corrupt_prob=0.5, corrupt_rounds=[1])  # list is a footgun
    with pytest.raises(ValueError, match="corrupt_scale"):
        FaultPlan(corrupt_scale=float("nan"))
    # plan corruption and the host tamper shim are mutually exclusive
    with pytest.raises(ValueError, match="tamper_hook"):
        FaultInjector(FaultPlan(corrupt_prob=1.0), 4,
                      host_tamper=lambda r, t: t)


# ------------------------------------------------------------------ dropout


def test_chaos_dropout_run_stays_finite():
    cfg = _tiny(mode="server",
                faults=FaultPlan(seed=2, dropout_prob=0.5))
    res = FedEngine(cfg).run()
    assert len(res.metrics.rounds) == 3
    _assert_finite(res.trainable)
    # the plan drops SOMEONE across three rounds at p=0.5 (seeded, so this
    # is deterministic), the mask records it, and dropped is observable
    dropped = [c for r in res.metrics.rounds for c in (r.dropped or [])]
    assert dropped, "seeded dropout plan never fired"
    for r in res.metrics.rounds:
        for c in r.dropped or []:
            assert r.mask[c] == 0.0


def test_all_clients_dropped_round_is_degraded_not_nan():
    """Every client eliminated -> the round keeps the previous global model,
    records degraded=True, and warns — instead of a 0/0 NaN mean."""
    cfg = _tiny(mode="server", num_rounds=2, eval_every=0,
                faults=FaultPlan(dropout_prob=1.0, dropout_rounds=(1,)))
    eng = FedEngine(cfg)
    res = eng.run()
    recs = res.metrics.rounds
    assert recs[0].degraded is False
    assert recs[1].degraded is True
    assert recs[1].mask == [0.0] * cfg.num_clients
    _assert_finite(res.trainable)


def test_all_masked_serverless_round_is_degraded_not_nan():
    eng = FedEngine(_tiny(mode="serverless", num_rounds=1))
    stacked = eng.progs.broadcast(eng.trainable0)
    out, consensus, rec = eng._serverless_round(
        0, stacked, eng.trainable0, np.zeros(4, np.float32))
    assert rec.degraded is True
    _assert_finite(consensus)
    for a, b in zip(_leaves(consensus), _leaves(eng.trainable0)):
        np.testing.assert_array_equal(a, b)  # consensus fell back


# ---------------------------------------------------------------- straggler


def test_chaos_straggler_stretches_info_passing():
    plan = FaultPlan(straggler_prob=1.0, straggler_delay_s=100.0)
    base = _tiny(mode="server", num_rounds=1)
    r0 = FedEngine(base).run().metrics.rounds[0]
    r1 = FedEngine(base.replace(faults=plan)).run().metrics.rounds[0]
    C = base.num_clients
    assert r1.straggler_s == [100.0] * C
    # sync = sum over C-1 targets, each 100 s late; async = slowest + 100
    assert r1.info_passing_sync_s == pytest.approx(
        r0.info_passing_sync_s + 100.0 * (C - 1))
    assert r1.info_passing_async_s == pytest.approx(
        r0.info_passing_async_s + 100.0)


def test_chaos_straggler_feeds_async_staleness():
    """An injected straggler's completion clock slips, so the async engine
    merges it late and staleness-decays it — the fault plan driving the
    simulated network clock."""
    delay = 1e6
    cfg = _cfg(sync="async", async_buffer=2, num_clients=3, num_rounds=1,
               weighted_agg=False,
               faults=FaultPlan(straggler_prob=1.0, straggler_delay_s=delay,
                                straggler_rounds=(0,)))
    eng = FedEngine(cfg)
    st = eng._init_async_state()
    before = st["next_done"].copy()
    _, _, rec = eng._async_round(0, eng.trainable0, None,
                                 np.ones(3, np.float32), st)
    assert rec.straggler_s == [delay] * 3
    # every arrival carried the injected delay: the simulated clock jumped
    # past it, and un-arrived clients still owe delayed completions
    assert st["clock"] >= before.min() + delay
    assert (st["next_done"] >= delay).all()


# --------------------------------------------------------------- corruption


def test_chaos_corruption_fails_ledger_auth_per_round_path():
    """FaultPlan corruption on the PER-ROUND path: commit fingerprints are
    taken before transport, verification after — the corrupted client fails
    chain auth, is excluded from the aggregate (auth-masked), and the model
    stays honest-magnitude. The unified replacement for what previously
    needed the fused-only ``fused_tamper`` hook."""
    plan = FaultPlan(corrupt_prob=1.0, corrupt_scale=1e6,
                     corrupt_rounds=(1,))
    # corrupt_prob=1.0 corrupts EVERY client in round 1 -> all-rejected
    # round keeps its starting params (collapse fallback)
    cfg = _tiny(mode="server", ledger=LedgerConfig(enabled=True),
                faults=plan)
    eng = FedEngine(cfg)
    assert eng._chunk_rounds(0) == 1  # plan faults force the per-round path
    res = eng.run()
    C = cfg.num_clients
    assert res.metrics.rounds[0].auth == [1.0] * C
    assert res.metrics.rounds[1].auth == [0.0] * C
    assert res.metrics.rounds[1].degraded is True
    assert res.metrics.rounds[2].auth == [1.0] * C
    # commit digests were honest; only the transported copies diverged
    assert res.ledger.verify_chain() == -1
    _assert_finite(res.trainable)
    assert all(np.abs(np.asarray(x)).max() < 1e3
               for x in _leaves(res.trainable))


def test_chaos_corruption_serverless_excluded_from_mix():
    plan = FaultPlan(seed=5, corrupt_prob=0.3, corrupt_rounds=(0,))
    cfg = _tiny(mode="serverless", ledger=LedgerConfig(enabled=True),
                num_rounds=2, faults=plan)
    eng = FedEngine(cfg)
    scales = eng.faults.transport_scales(0)
    assert scales is not None and (scales > 0).any()
    res = eng.run()
    bad = [c for c in range(cfg.num_clients) if scales[c] > 0]
    rec = res.metrics.rounds[0]
    assert [rec.auth[c] for c in bad] == [0.0] * len(bad)
    assert res.ledger.verify_chain() == -1
    # the sender's own carry stays its honest local state (mix_recv): no
    # 1e6-magnitude value may survive anywhere in the consensus params
    _assert_finite(res.trainable)
    assert all(np.abs(np.asarray(x)).max() < 1e3
               for x in _leaves(res.trainable))


@pytest.mark.parametrize("aggregator", ["trimmed_mean", "median"])
def test_robust_aggregator_recovers_corrupted_accuracy(aggregator):
    """Without any ledger, a 1-of-4 corrupted client rides into aggregation.
    What the robust rules guarantee is a BOUND, not the clean run's
    accuracy: every coordinate of the aggregate is an order statistic of the
    four rows that lies inside the honest clients' range. With 4 clients the
    trimmed mean drops one value from each end and the +1e6 row is always
    the largest, so the aggregate is the mean of the upper two honest values
    on every coordinate: inside the range, but shifted one way everywhere,
    which two rounds of a tiny model turn into 0.2 of accuracy. So the run
    is held to finite, honest-magnitude parameters, and the guarantee is
    checked on one round, where the honest updates can be seen."""
    from bcfl_tpu.fed.synthetic import synthetic_round_inputs

    plan = FaultPlan(corrupt_prob=0.26, corrupt_scale=1e6, seed=2)
    cfg = _cfg(mode="server", aggregator=aggregator, faults=plan)
    # the seeded plan must actually corrupt exactly one of the four clients
    # each round for the claim to mean anything
    for rnd in range(cfg.num_rounds):
        row = plan.transport_scales(rnd, cfg.num_clients)
        assert row is not None and (row > 0).sum() == 1
    eng = FedEngine(cfg)
    res = eng.run()
    _assert_finite(res.trainable)
    assert all(np.abs(np.asarray(x)).max() < 1e3
               for x in _leaves(res.trainable))

    # one round through the engine's own programs (built with the rule):
    # honest updates, transport as FaultPlan(corrupt_scale=1e6) does it
    batches, weights, rngs = synthetic_round_inputs(
        eng.mesh, steps=2, batch=4, seq=16, vocab_size=512)
    start = eng.model.init(jax.random.key(0), batches["ids"][0, 0],
                           batches["mask"][0, 0])["params"]
    honest, _ = eng.progs.client_updates(start, None, batches, rngs)
    bad = 2
    sent = jax.tree.map(lambda x: x.at[bad].add(1e6), honest)
    agg = eng.progs.collapse(sent, weights, start)
    for got, rows, hon in zip(_leaves(agg), _leaves(sent), _leaves(honest)):
        hon = np.delete(hon, bad, axis=0)
        assert (got >= hon.min(0)).all() and (got <= hon.max(0)).all()
        if aggregator == "median":
            want = np.median(rows, axis=0)
        else:  # t = ceil(0.2 * 4) = 1 from each end
            want = np.sort(rows, axis=0)[1:-1].mean(0)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-8)


def test_mean_aggregator_destroyed_by_corruption():
    """Contrast case: the plain mean has no defense without the ledger —
    the 1e6 perturbation lands in the global model. (If this ever starts
    passing with honest magnitudes, the corruption stage is broken and the
    robust-aggregator recovery test above is vacuous.)"""
    plan = FaultPlan(corrupt_prob=0.26, corrupt_scale=1e6, seed=2)
    res = FedEngine(_tiny(mode="server", num_rounds=1, eval_every=0,
                          faults=plan)).run()
    assert any(np.abs(np.asarray(x)).max() > 1e3
               for x in _leaves(res.trainable))


# ------------------------------------------------------------ crash-resume


def test_crash_resume_bit_identical(tmp_path):
    """Kill the loop at round 2 of 3, restart from the checkpoint: the
    resumed run must reproduce the uninterrupted run's final model
    BIT-FOR-BIT (same RNG streams, same programs, verified checkpoints)."""
    base = _tiny(mode="server", num_rounds=3, eval_every=0,
                 checkpoint_dir=str(tmp_path / "a"), checkpoint_every=1)
    res_a = FedEngine(base).run()

    crash = base.replace(checkpoint_dir=str(tmp_path / "b"),
                         faults=FaultPlan(crash_at_round=2))
    with pytest.raises(SimulatedCrash) as ei:
        FedEngine(crash).run()
    assert ei.value.round == 2
    # resume with the SAME plan (the CLI workflow): the crash models one
    # host failure and must not re-fire on the resumed run
    res_b = FedEngine(crash).run(resume=True)
    assert [r.round for r in res_b.metrics.rounds] == [2]
    for a, b in zip(_leaves(res_a.trainable), _leaves(res_b.trainable)):
        np.testing.assert_array_equal(a, b)


def test_crash_resume_serverless_stacked_state(tmp_path):
    """Serverless crash-resume must also restore the per-client stacked
    params (not just the consensus view) bit-for-bit."""
    base = _tiny(mode="serverless", num_rounds=3, eval_every=0,
                 checkpoint_dir=str(tmp_path / "a"), checkpoint_every=1)
    res_a = FedEngine(base).run()
    crash = base.replace(checkpoint_dir=str(tmp_path / "b"),
                         faults=FaultPlan(crash_at_round=2))
    with pytest.raises(SimulatedCrash):
        FedEngine(crash).run()
    res_b = FedEngine(crash).run(resume=True)
    for a, b in zip(_leaves(res_a.trainable), _leaves(res_b.trainable)):
        np.testing.assert_array_equal(a, b)


def test_crash_fires_despite_resume_flag_without_checkpoint(tmp_path):
    """The one-host-failure suppression is gated on an ACTUAL restore, not
    the resume flag: a standing --resume over a fresh checkpoint dir must
    still crash, or the chaos experiment silently never happens."""
    cfg = _tiny(mode="server", num_rounds=2, eval_every=0,
                checkpoint_dir=str(tmp_path / "fresh"), checkpoint_every=1,
                faults=FaultPlan(crash_at_round=0))
    with pytest.raises(SimulatedCrash):
        FedEngine(cfg).run(resume=True)


# ------------------------------------------------- crash-safe checkpointing


def _state(v: float):
    return {"trainable": {"w": np.full((8, 4), v, np.float32)},
            "seed": np.int64(42)}


def test_truncated_newest_checkpoint_falls_back(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 0, _state(0.0), ledger_json="[]")
    p1 = save_checkpoint(d, 1, _state(1.0))
    # simulate a pre-atomic writer dying mid-save: the round_ dir exists
    # but its tree payload is gone
    for f in glob.glob(os.path.join(p1, "**"), recursive=True):
        if os.path.isfile(f):
            os.remove(f)
    r, state, ledger_json = restore_latest(d)
    assert r == 0
    np.testing.assert_array_equal(state["trainable"]["w"],
                                  _state(0.0)["trainable"]["w"])
    assert ledger_json == "[]"


def test_corrupted_newest_checkpoint_digest_falls_back(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 0, _state(0.0))
    p1 = save_checkpoint(d, 1, _state(1.0))
    # flip payload bytes in the newest tree: either the store's own
    # integrity check or the committed params digest must reject it
    data_files = sorted(
        (f for f in glob.glob(os.path.join(p1, "**"), recursive=True)
         if os.path.isfile(f)),
        key=os.path.getsize, reverse=True)
    with open(data_files[0], "r+b") as f:
        f.seek(-16, os.SEEK_END)
        f.write(b"\xde\xad\xbe\xef" * 4)
    r, state, _ = restore_latest(d)
    assert r == 0


def test_all_checkpoints_invalid_returns_none(tmp_path):
    d = str(tmp_path)
    p0 = save_checkpoint(d, 0, _state(0.0))
    for f in glob.glob(os.path.join(p0, "**"), recursive=True):
        if os.path.isfile(f):
            os.remove(f)
    assert restore_latest(d) is None


def test_interrupted_save_is_invisible(tmp_path):
    """A staging directory (simulated crash BEFORE the atomic rename) must
    never be picked up by the newest-first scan."""
    d = str(tmp_path)
    save_checkpoint(d, 0, _state(0.0))
    os.makedirs(os.path.join(d, ".staging.round_000001"))
    r, _, _ = restore_latest(d)
    assert r == 0
    # and the next save of that round cleans the leftover and commits
    save_checkpoint(d, 1, _state(1.0))
    r, state, _ = restore_latest(d)
    assert r == 1 and state["trainable"]["w"][0, 0] == 1.0


def test_engine_resumes_from_valid_after_truncation(tmp_path):
    """End-to-end: an engine whose NEWEST checkpoint is truncated resumes
    from the previous valid one instead of raising."""
    cfg = _tiny(mode="server", num_rounds=2, eval_every=0,
                checkpoint_dir=str(tmp_path), checkpoint_every=1)
    FedEngine(cfg).run()
    newest = os.path.join(str(tmp_path), "round_000001")
    for f in glob.glob(os.path.join(newest, "**"), recursive=True):
        if os.path.isfile(f):
            os.remove(f)
    res = FedEngine(cfg.replace(num_rounds=3)).run(resume=True)
    # resumed from round 0's checkpoint -> rounds 1 and 2 execute
    assert [r.round for r in res.metrics.rounds] == [1, 2]


# ---------------------------------------------------- aggregator compilation


@pytest.mark.parametrize("aggregator",
                         ["mean", "trimmed_mean", "median", "krum"])
def test_aggregator_compiles_once_across_rounds(aggregator, monkeypatch):
    """Every aggregation rule lives INSIDE the compiled round program:
    switching `aggregator` swaps executables at build time and a 3-round run
    never retraces (cache size exactly 1 on the hot program)."""
    monkeypatch.setenv("BCFL_PROGRAM_CACHE", "0")
    eng = FedEngine(_tiny(mode="server", aggregator=aggregator))
    res = eng.run()
    assert len(res.metrics.rounds) == 3
    assert eng.progs.server_round._cache_size() == 1, aggregator
    _assert_finite(res.trainable)


def test_aggregator_masked_clients_excluded():
    """Mask-awareness inside the compiled program: a masked client's update
    must not shift the robust aggregate (order statistics over participants
    only, not a weighted blend)."""
    import jax.numpy as jnp

    from bcfl_tpu.parallel import gspmd

    tree = {"w": jnp.stack([jnp.full((3,), v) for v in (1.0, 2.0, 3.0, 1e9)])}
    w = jnp.asarray([1.0, 1.0, 1.0, 0.0])
    np.testing.assert_allclose(
        np.asarray(gspmd.masked_median(tree, w)["w"]), 2.0)
    np.testing.assert_allclose(
        np.asarray(gspmd.masked_trimmed_mean(tree, w, 0.2)["w"]), 2.0)
    picked = np.asarray(gspmd.masked_krum(tree, w, 0.2)["w"])
    assert picked.max() < 1e3  # never the masked outlier
    # all-masked -> fallback, not NaN
    fb = {"w": jnp.full((3,), 7.0)}
    out = gspmd.masked_median(tree, jnp.zeros(4), fallback=fb)
    np.testing.assert_allclose(np.asarray(out["w"]), 7.0)


def test_legacy_tamper_kwargs_are_deprecated_shims():
    cfg = _tiny(num_rounds=1, ledger=LedgerConfig(enabled=True))
    with pytest.warns(DeprecationWarning, match="FaultPlan"):
        eng = FedEngine(cfg, tamper_hook=lambda r, t: t)
    assert eng.faults.host_tamper is not None
    res = eng.run()  # the shim still runs the faithful byte-hash flow
    assert res.ledger.verify_chain() == -1
