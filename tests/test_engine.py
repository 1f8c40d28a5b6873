"""End-to-end engine runs: the TPU twin of the reference's scale-down smoke
method (NUM_CLIENTS=2 / NUM_ROUNDS=2 BioBERT notebook — SURVEY.md §4), across
all four mode combinations plus ledger, faithful, async, and resume."""

import numpy as np
import pytest

from bcfl_tpu.config import FedConfig, LedgerConfig, PartitionConfig, TopologyConfig
from bcfl_tpu.fed.engine import FedEngine

pytestmark = pytest.mark.slow  # engine-suite tier: compile-heavy on the
# 8-device CPU mesh; the tier-1 'not slow' window runs the chaos matrix
# (tests/test_faults.py) as its fast engine coverage instead


def _cfg(**kw):
    base = dict(
        dataset="synthetic", num_labels=2, seq_len=32, batch_size=16,
        vocab_size=512, model="tiny-bert", num_clients=4, num_rounds=2,
        learning_rate=3e-4, max_local_batches=4,
        partition=PartitionConfig(kind="iid", iid_samples=64),
    )
    base.update(kw)
    return FedConfig(**base)


def test_server_iid_two_rounds_learns():
    res = FedEngine(_cfg(mode="server")).run()
    accs = res.metrics.global_accuracies
    assert len(accs) == 2
    assert accs[-1] > 0.55  # up from ~0.5 chance
    assert res.metrics.model_size_gb > 0
    assert res.metrics.rounds[0].info_passing_sync_s > \
        res.metrics.rounds[0].info_passing_async_s


def test_serverless_gossip_two_rounds():
    res = FedEngine(_cfg(mode="serverless")).run()
    assert len(res.metrics.global_accuracies) == 2
    assert res.metrics.rounds[-1].train_acc > 0.5


def test_serverless_noniid_contiguous():
    cfg = _cfg(
        mode="serverless", num_clients=4,
        partition=PartitionConfig(kind="contiguous", stride=100, train_span=80,
                                  test_span=20, test_mode="trailing"),
        weighted_agg=False,  # reference serverless unweighted mean
    )
    res = FedEngine(cfg).run()
    assert len(res.metrics.rounds) == 2
    assert all(len(r.local_acc) == 4 for r in res.metrics.rounds)


def test_faithful_sequential_mode():
    res = FedEngine(_cfg(mode="serverless", faithful=True, num_clients=3)).run()
    assert len(res.metrics.rounds) == 2
    assert res.metrics.rounds[-1].train_acc > 0.4


def test_anomaly_filter_gates_round():
    cfg = _cfg(num_clients=10, num_rounds=1,
               topology=TopologyConfig(anomaly_filter="pagerank"))
    res = FedEngine(cfg).run()
    rec = res.metrics.rounds[0]
    assert rec.anomalies == [0, 4, 7, 9]  # golden set on the reference graph
    assert [rec.mask[a] for a in rec.anomalies] == [0.0] * 4


def test_ledger_detects_tampering():
    """BC-FL flow: tampered in-flight update fails authentication and is
    excluded; chain stays valid."""
    tampered_rounds = []

    def tamper(rnd, host_tree):
        import jax

        out = jax.tree.map(lambda x: np.array(x, copy=True), host_tree)
        # flip one weight of client 2 in the first leaf
        first = jax.tree.leaves(out)[0]
        first[2] = first[2] + 99.0
        tampered_rounds.append(rnd)
        return out

    cfg = _cfg(mode="server", ledger=LedgerConfig(enabled=True))
    eng = FedEngine(cfg, tamper_hook=tamper)
    res = eng.run()
    assert res.ledger is not None
    assert res.ledger.verify_chain() == -1
    assert res.metrics.ledger["chain_ok"] == 1.0
    assert res.metrics.ledger["reduction"] > 0.99
    assert tampered_rounds  # hook ran


def test_async_buffered_rounds():
    cfg = _cfg(sync="async", async_buffer=2, num_rounds=3)
    res = FedEngine(cfg).run()
    assert len(res.metrics.rounds) == 3
    assert res.metrics.global_accuracies[-1] > 0.5


def test_checkpoint_resume(tmp_path):
    # "crash" after round 0 ...
    cfg = _cfg(mode="server", num_rounds=1, checkpoint_dir=str(tmp_path),
               checkpoint_every=1)
    res1 = FedEngine(cfg).run()
    assert len(res1.metrics.rounds) == 1

    # ... resume a 2-round run: only the second round executes
    res2 = FedEngine(cfg.replace(num_rounds=2)).run(resume=True)
    assert len(res2.metrics.rounds) == 1
    assert res2.metrics.rounds[0].round == 1


def test_lora_engine_run():
    res = FedEngine(_cfg(mode="server", lora_rank=4, num_rounds=1)).run()
    assert len(res.metrics.rounds) == 1
    # trainable is the adapter tree; merged params include the frozen base
    import jax

    n_train = sum(x.size for x in jax.tree.leaves(res.trainable))
    n_full = sum(x.size for x in jax.tree.leaves(res.params))
    assert n_train < n_full / 5
    # the task head trains IN FULL under LoRA (a frozen random-init
    # classifier would cap accuracy); its leaves live in the adapter tree
    assert any("classifier" in k for k in res.trainable)


def test_all_tampered_round_keeps_model():
    """If EVERY client's shipped update fails ledger authentication, the
    global model must not move (regression: collapse fallback)."""
    import jax

    def tamper_all(rnd, host_tree):
        out = jax.tree.map(lambda x: np.array(x, copy=True), host_tree)
        first = jax.tree.leaves(out)[0]
        first += 1.0  # every client's update modified in flight
        return out

    cfg = _cfg(mode="server", num_rounds=1, ledger=LedgerConfig(enabled=True))
    eng = FedEngine(cfg, tamper_hook=tamper_all)
    before = jax.device_get(eng.trainable0)
    res = eng.run()
    after = jax.device_get(res.trainable)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_allclose(a, b, atol=1e-7)


def test_faithful_ledger_detects_tampering():
    """Faithful (sequential shared-model) mode must authenticate snapshots
    too: a tampered client is excluded from the end-of-round average and its
    rejection is recorded (regression: faithful path skipped authentication)."""
    import jax

    def tamper_client0(rnd, host_tree):
        out = jax.tree.map(lambda x: np.array(x, copy=True), host_tree)
        first = jax.tree.leaves(out)[0]
        first[0] = first[0] + 99.0
        return out

    cfg = _cfg(mode="serverless", faithful=True, num_clients=3, num_rounds=1,
               ledger=LedgerConfig(enabled=True))
    res = FedEngine(cfg, tamper_hook=tamper_client0).run()
    rec = res.metrics.rounds[0]
    assert rec.auth == [0.0, 1.0, 1.0]
    assert res.ledger.verify_chain() == -1  # chain itself intact


def test_faithful_all_masked_keeps_params():
    """A faithful round where every client is excluded must keep the round's
    starting params (regression: used to zero the model via mask/max(sum,1))."""
    import jax

    eng = FedEngine(_cfg(mode="serverless", faithful=True, num_clients=3,
                         num_rounds=1))
    out, rec = eng._faithful_round(0, eng.trainable0, np.zeros(3, np.float32))
    for a, b in zip(jax.tree.leaves(jax.device_get(out)),
                    jax.tree.leaves(jax.device_get(eng.trainable0))):
        np.testing.assert_array_equal(a, b)


def test_async_compute_cost_from_examples():
    """The async network clock's local-compute term is proportional to each
    client's example count (regression: was uniform np.ones)."""
    eng = FedEngine(_cfg(sync="async", num_clients=4, num_rounds=1))
    n_ex = np.array([10.0, 20.0, 30.0, 40.0])
    eng._round_batches = lambda rnd: (None, n_ex)
    st = eng._init_async_state()
    transfer = np.array([
        eng.graph.shortest_path_times(eng._payload_gb())[c, eng.info_source]
        if c != eng.info_source else 0.0 for c in range(4)])
    np.testing.assert_allclose(
        st["duration"] - transfer, n_ex / n_ex.mean(), rtol=1e-6)


def test_async_staleness_downweights_slow_client():
    """A client whose simulated link is slow accumulates staleness; when it
    finally arrives its merge weight is decay**staleness, not full weight."""
    cfg = _cfg(sync="async", async_buffer=1, num_clients=3, num_rounds=1,
               weighted_agg=False)
    eng = FedEngine(cfg)
    st = eng._init_async_state()
    st["next_done"] = np.array([1e9, 1.0, 2.0])  # client 0 is very slow
    mask = np.ones(3, np.float32)
    trainable, stacked = eng.trainable0, None
    for rnd in range(3):
        trainable, stacked, rec = eng._async_round(
            rnd, trainable, stacked, mask, st)
    assert st["global_version"] == 3
    assert st["version"][0] == 0  # never merged
    # force the slow client to arrive next: staleness = 3
    st["next_done"][0] = 0.0
    _, _, rec = eng._async_round(3, trainable, stacked, mask, st)
    decay = cfg.staleness_decay
    assert rec.async_alpha[0] == pytest.approx(decay ** 3)
    assert rec.async_alpha[1] == 0.0 and rec.async_alpha[2] == 0.0
    assert st["version"][0] == st["global_version"]


def test_async_merge_scale_shrinks_stale_step():
    """The factor actually applied to the merged delta (collapse normalizes
    weights away) must shrink with staleness: a lone stale arrival steps by
    decay**staleness, fresh arrivals step at full strength."""
    cfg = _cfg(sync="async", num_clients=3, weighted_agg=False)
    eng = FedEngine(cfg)
    n_ex = np.array([10.0, 10.0, 10.0])
    fresh = np.array([1.0, 0.0, 0.0], np.float32)
    stale = np.array([cfg.staleness_decay ** 3, 0.0, 0.0], np.float32)
    assert eng._async_merge_scale(fresh, [0], n_ex) == pytest.approx(1.0)
    assert eng._async_merge_scale(stale, [0], n_ex) == pytest.approx(
        cfg.staleness_decay ** 3)
    # example weighting: scale is decayed-weight share of the example mass
    eng_w = FedEngine(_cfg(sync="async", num_clients=3, weighted_agg=True))
    a = np.array([0.5 * 10.0, 1.0 * 20.0, 0.0], np.float32)  # alpha * n_ex
    assert eng_w._async_merge_scale(a, [0, 1], np.array([10.0, 20.0, 5.0])) \
        == pytest.approx((5.0 + 20.0) / 30.0)


def test_rounds_per_dispatch_matches_per_round_path():
    """Fusing rounds into one dispatch (rounds_per_dispatch) must reproduce
    the per-round path bit-for-bit in results and keep the eval cadence."""
    import jax

    base = _cfg(mode="server", num_rounds=4, eval_every=2)
    r1 = FedEngine(base).run()
    rk = FedEngine(base.replace(rounds_per_dispatch=4)).run()

    assert len(rk.metrics.rounds) == 4
    # eval happened exactly at rounds 1 and 3 on both paths
    evald = [r.round for r in rk.metrics.rounds if r.global_acc is not None]
    assert evald == [1, 3]
    np.testing.assert_allclose(
        rk.metrics.global_accuracies, r1.metrics.global_accuracies, atol=1e-6)
    for a, b in zip(jax.tree.leaves(jax.device_get(rk.trainable)),
                    jax.tree.leaves(jax.device_get(r1.trainable))):
        np.testing.assert_allclose(a, b, atol=1e-5)
    # per-round train stats line up too
    for ra, rb in zip(rk.metrics.rounds, r1.metrics.rounds):
        assert ra.round == rb.round
        np.testing.assert_allclose(ra.train_loss, rb.train_loss, rtol=1e-4)


def test_rounds_per_dispatch_ineligible_configs_fall_back():
    """Ledger / anomaly-filter / faithful / async configs must silently use
    the per-round path (the host is needed between rounds); parallel sync
    serverless IS eligible (gossip_rounds)."""
    cfg = _cfg(mode="serverless", num_rounds=2, rounds_per_dispatch=8,
               eval_every=2)
    eng = FedEngine(cfg)
    assert eng._chunk_rounds(0) == 2  # bounded by remaining rounds
    cfg_f = _cfg(mode="serverless", num_rounds=2, rounds_per_dispatch=8,
                 faithful=True)
    assert FedEngine(cfg_f)._chunk_rounds(0) == 1
    cfg2 = _cfg(mode="server", num_rounds=2, rounds_per_dispatch=8,
                ledger=LedgerConfig(enabled=True))
    assert FedEngine(cfg2)._chunk_rounds(0) == 1
    cfg3 = _cfg(mode="server", num_clients=10, num_rounds=2,
                rounds_per_dispatch=8,
                topology=TopologyConfig(anomaly_filter="pagerank"))
    assert FedEngine(cfg3)._chunk_rounds(0) == 1
    # eligible config: bounded by eval boundary and remaining rounds
    cfg4 = _cfg(mode="server", num_rounds=3, rounds_per_dispatch=8,
                eval_every=2)
    eng4 = FedEngine(cfg4)
    assert eng4._chunk_rounds(0) == 2
    assert eng4._chunk_rounds(2) == 1


def test_rounds_per_dispatch_resampled_partition():
    """Per-round resampling (batches differ each round) goes through the
    stacked-batches variant and still matches the per-round path."""
    base = _cfg(mode="server", num_rounds=2, eval_every=2,
                partition=PartitionConfig(kind="iid", iid_samples=64,
                                          resample_each_round=True))
    r1 = FedEngine(base).run()
    rk = FedEngine(base.replace(rounds_per_dispatch=2)).run()
    np.testing.assert_allclose(
        rk.metrics.global_accuracies, r1.metrics.global_accuracies, atol=1e-6)


def test_serverless_chunk_lazy_consensus_end_of_run():
    """With eval and checkpointing off, fused chunks skip the consensus
    collapse entirely until the final round — the end-of-run trainable must
    still match the per-round path."""
    import jax

    base = _cfg(mode="serverless", num_rounds=4, eval_every=0)
    r1 = FedEngine(base).run()
    rk = FedEngine(base.replace(rounds_per_dispatch=2)).run()
    for a, b in zip(jax.tree.leaves(jax.device_get(rk.trainable)),
                    jax.tree.leaves(jax.device_get(r1.trainable))):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_serverless_rounds_per_dispatch_matches_per_round_path():
    """Fused gossip rounds (gossip_rounds / gossip_rounds_static) must
    reproduce the per-round serverless path: same per-client params, same
    consensus accuracies, same eval cadence, on both the round-static and
    resampled partitions."""
    import jax

    for part in (PartitionConfig(kind="iid", iid_samples=64),
                 PartitionConfig(kind="iid", iid_samples=64,
                                 resample_each_round=True)):
        base = _cfg(mode="serverless", num_rounds=4, eval_every=2,
                    partition=part)
        r1 = FedEngine(base).run()
        rk = FedEngine(base.replace(rounds_per_dispatch=4)).run()
        assert len(rk.metrics.rounds) == 4
        evald = [r.round for r in rk.metrics.rounds
                 if r.global_acc is not None]
        assert evald == [1, 3]
        np.testing.assert_allclose(
            rk.metrics.global_accuracies, r1.metrics.global_accuracies,
            atol=1e-6)
        for a, b in zip(jax.tree.leaves(jax.device_get(rk.trainable)),
                        jax.tree.leaves(jax.device_get(r1.trainable))):
            np.testing.assert_allclose(a, b, atol=1e-5)
        for ra, rb in zip(rk.metrics.rounds, r1.metrics.rounds):
            assert ra.round == rb.round
            np.testing.assert_allclose(ra.train_loss, rb.train_loss,
                                       rtol=1e-4)


def test_ledger_fingerprint_path_no_full_transfer(monkeypatch):
    """Without a tamper hook the ledger must use device-side fingerprints:
    jax.device_get of the full stacked tree is the r03 bottleneck this
    replaces (VERDICT r03 weak #4). Chain still valid, auth all-pass, and
    the run records a 'ledger' StepClock phase."""
    import jax

    import bcfl_tpu.fed.engine as engine_mod

    calls = []
    real_device_get = jax.device_get

    def spying_get(x):
        calls.append(sum(np.asarray(l).nbytes
                         for l in jax.tree.leaves(real_device_get(x))))
        return real_device_get(x)

    cfg = _cfg(mode="server", ledger=LedgerConfig(enabled=True))
    eng = FedEngine(cfg)
    monkeypatch.setattr(engine_mod.jax, "device_get", spying_get)
    res = eng.run()
    # checkpointing is off, so nothing should have pulled a full param tree
    assert not calls, f"full-tree device_get in ledger path: {calls}"
    assert res.ledger.verify_chain() == -1
    assert all(r.auth == [1.0] * cfg.num_clients for r in res.metrics.rounds)
    assert res.metrics.phases["ledger"]["count"] > 0
    assert res.metrics.ledger["reduction"] > 0.99


def test_ledger_fused_rounds_match_per_round():
    """VERDICT r03 weak #4: the ledger no longer disables round fusion. A
    fused ledger run must produce the same chain length, all-pass auth, and
    (numerically close) final params as the per-round ledger run."""
    import jax

    cfg = _cfg(mode="server", num_rounds=4,
               ledger=LedgerConfig(enabled=True))
    res_per = FedEngine(cfg).run()
    res_fused = FedEngine(cfg.replace(rounds_per_dispatch=2,
                                      eval_every=2)).run()
    assert len(res_fused.metrics.rounds) == 4
    C = cfg.num_clients
    assert len(res_fused.ledger) == 4 * C == len(res_per.ledger)
    assert res_fused.ledger.verify_chain() == -1
    assert all(r.auth == [1.0] * C for r in res_fused.metrics.rounds)
    for a, b in zip(jax.tree.leaves(jax.device_get(res_per.trainable)),
                    jax.tree.leaves(jax.device_get(res_fused.trainable))):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_ledger_fused_serverless_gossip():
    cfg = _cfg(mode="serverless", num_rounds=2, rounds_per_dispatch=2,
               eval_every=2, ledger=LedgerConfig(enabled=True))
    res = FedEngine(cfg).run()
    assert len(res.ledger) == 2 * cfg.num_clients
    assert res.ledger.verify_chain() == -1
    assert res.metrics.ledger["chain_ok"] == 1.0


def test_final_round_always_evaluated():
    """eval_every=2 with an odd round count: the run must still end with a
    final-round evaluation (final_acc is reported as the headline number)."""
    res = FedEngine(_cfg(mode="server", num_rounds=3, eval_every=2)).run()
    evald = [r.round for r in res.metrics.rounds if r.global_acc is not None]
    assert evald == [1, 2]  # the eval_every boundary AND the forced final


def test_profile_dir_writes_trace(tmp_path):
    """FedConfig.profile_dir wraps the run in jax.profiler tracing; the
    trace directory must actually materialize (the reference's only
    profiling was psutil + wall-clock — SURVEY.md §5)."""
    import os

    cfg = _cfg(mode="server", num_rounds=1, profile_dir=str(tmp_path / "tr"))
    FedEngine(cfg).run()
    trace_files = [os.path.join(r, f)
                   for r, _, fs in os.walk(tmp_path / "tr") for f in fs]
    assert trace_files, "profiler trace directory is empty"
    # the engine's spans sit in the trace as fed.* host events, on the
    # profiler's clock, each with its round (and, on an enqueue, its program)
    from jax.profiler import ProfileData

    (pb,) = [f for f in trace_files if f.endswith(".xplane.pb")]
    spans = [(ev.name, dict(ev.stats))
             for plane in ProfileData.from_file(pb).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("fed.")]
    names = {n for n, _ in spans}
    assert {"fed.control_plane", "fed.round_program", "fed.round_program/enqueue",
            "fed.round_program/wait", "fed.post_round"} <= names
    assert all(st["round"] == 0 for _, st in spans)
    assert {st["program"] for n, st in spans
            if n == "fed.round_program/enqueue"} == {"server_round"}


def test_ledger_fused_transport_corruption_fails_auth():
    """VERDICT r04 weak #2: fused-mode ledger auth must be a real check, not
    an accounting identity. The fused ``*_fp`` programs commit fingerprints
    BEFORE a simulated-transport stage and authenticate the post-transport
    buffer — so a corrupted update FAILS chain auth AND is excluded from the
    aggregate by the in-graph gate, while honest clients pass."""
    import jax

    cfg = _cfg(mode="server", num_rounds=2, rounds_per_dispatch=2,
               eval_every=2, ledger=LedgerConfig(enabled=True))
    C = cfg.num_clients

    def corrupt(rnd):
        if rnd == 1:
            row = np.zeros((C,), np.float32)
            row[1] = 1e6  # must be gated out, not averaged into the model
            return row
        return None

    eng = FedEngine(cfg, fused_tamper=corrupt)
    assert eng._chunk_rounds(0) == 2  # the CORRUPTED run still fuses
    res = eng.run()
    assert res.metrics.rounds[0].auth == [1.0] * C
    assert res.metrics.rounds[1].auth == [1.0, 0.0] + [1.0] * (C - 2)
    # the chain itself stays intact: commit digests were honest, only the
    # transported copies diverged
    assert res.ledger.verify_chain() == -1
    # in-graph gating: the 1e6 perturbation never reached the global mean
    assert all(np.isfinite(x).all() and np.abs(x).max() < 1e3
               for x in jax.tree.leaves(jax.device_get(res.trainable)))


def test_ledger_fused_serverless_corruption_fails_auth():
    """Serverless twin: in-flight corruption poisons only the RECEIVED
    copies — the corrupted client fails auth, its state is excluded from
    every mix, and all carried params stay honest-magnitude."""
    import jax

    cfg = _cfg(mode="serverless", num_rounds=2, rounds_per_dispatch=2,
               eval_every=2, ledger=LedgerConfig(enabled=True))
    C = cfg.num_clients
    row = np.zeros((C,), np.float32)
    row[0] = 1e6
    res = FedEngine(
        cfg, fused_tamper=lambda rnd: row if rnd == 0 else None).run()
    assert res.metrics.rounds[0].auth == [0.0] + [1.0] * (C - 1)
    assert res.metrics.rounds[1].auth == [1.0] * C
    assert res.ledger.verify_chain() == -1
    # the sender's own carry is its honest local state, so the consensus
    # params never reflect the transport perturbation
    assert all(np.isfinite(x).all() and np.abs(x).max() < 1e3
               for x in jax.tree.leaves(jax.device_get(res.trainable)))


def test_fused_round_records_marked():
    """VERDICT r04 weak #5: fused-round records must be distinguishable from
    measured per-round records — ``fused=True`` with the real chunk wall in
    ``wall_chunk_s`` (wall_s is its even split), per-round path unmarked."""
    base = _cfg(mode="server", num_rounds=2, eval_every=2)
    fused = FedEngine(base.replace(rounds_per_dispatch=2)).run()
    for r in fused.metrics.rounds:
        assert r.fused is True
        assert r.wall_chunk_s is not None
        assert r.wall_s == pytest.approx(r.wall_chunk_s / 2)
    plain = FedEngine(base).run()
    assert all(r.fused is False and r.wall_chunk_s is None
               for r in plain.metrics.rounds)


def test_model_size_gb_accepts_scalar_leaves():
    """ADVICE r04: host-side trees may carry plain Python scalars (e.g. a
    checkpoint state dict); size must fall back per-leaf instead of raising."""
    from bcfl_tpu.metrics import model_size_gb

    tree = {"w": np.zeros((4, 4), np.float32), "seed": 7, "lr": 1e-3,
            "n": np.int64(3)}
    gb = model_size_gb(tree)
    assert gb > 0
    assert gb == pytest.approx((64 + 8 + 8 + 8) / 1e9)


def test_fused_tamper_on_per_round_path_fails_loudly():
    """A fused_tamper corruption request for a round that runs the
    per-round path (here: rounds_per_dispatch=1) must raise, not be
    silently ignored — a vacuous all-pass auth would look like a
    verification."""
    cfg = _cfg(mode="server", num_rounds=1,
               ledger=LedgerConfig(enabled=True))
    C = cfg.num_clients
    eng = FedEngine(cfg, fused_tamper=lambda rnd: np.ones((C,), np.float32))
    with pytest.raises(ValueError, match="per-round path"):
        eng.run()
