"""clients x tp composition: a federated LoRA round where each client's
forward/backward is tensor-parallel over a 'tp' mesh axis — the BASELINE.json
Llama-LoRA config's sharding story, exercised on the 8-device CPU mesh, both
through the library helpers and end-to-end through FedEngine.run(config)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcfl_tpu.config import FedConfig, LedgerConfig, PartitionConfig
from bcfl_tpu.core.mesh import (
    distributed_init,
    fed_tp_mesh,
    pod_client_mesh,
    pod_devices,
)
from bcfl_tpu.fed.engine import FedEngine
from bcfl_tpu.models import build
from bcfl_tpu.models.llama import LORA_TARGETS, tp_specs
from bcfl_tpu.models import lora as lora_lib
from bcfl_tpu.parallel.fed_tp import build_fed_tp_round, stack_adapters

pytestmark = pytest.mark.slow  # engine-suite tier: compile-heavy on the
# 8-device CPU mesh; the tier-1 'not slow' window runs the chaos matrix
# (tests/test_faults.py) as its fast engine coverage instead


def test_distributed_init_single_process_noop():
    assert distributed_init() is False
    assert jax.process_count() == 1


def test_pod_devices_single_process():
    assert pod_devices() == list(jax.devices())
    assert pod_client_mesh(8).num_clients == 8


def test_fed_tp_mesh_shape_and_validation():
    mesh = fed_tp_mesh(4, 2)
    assert mesh.axis_names == ("clients", "tp")
    assert mesh.devices.shape == (4, 2)
    with pytest.raises(ValueError):
        fed_tp_mesh(8, 2)  # 16 devices needed, 8 available


def test_fed_tp_lora_round():
    C, TP = 4, 2
    mesh = fed_tp_mesh(C, TP)
    model = build("tiny-llama", num_labels=2)

    B, S = 4, 32
    ids = jnp.ones((B, S), jnp.int32)
    frozen = model.init(jax.random.key(0), ids, ids)["params"]
    specs = tp_specs(frozen, axis="tp")
    from jax.sharding import NamedSharding

    frozen = jax.device_put(
        frozen, jax.tree.map(lambda s: NamedSharding(mesh, s), specs))

    adapters = lora_lib.init_lora(jax.random.key(1), frozen, rank=2,
                                  targets=LORA_TARGETS)
    stacked = stack_adapters(mesh, adapters, C)

    rng = np.random.default_rng(0)
    steps = 2
    batches = {
        "ids": jnp.asarray(rng.integers(0, 256, (C, steps, B, S)), jnp.int32),
        "mask": jnp.ones((C, steps, B, S), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, 2, (C, steps, B)), jnp.int32),
        "example_mask": jnp.ones((C, steps, B), jnp.float32),
    }
    rngs = jax.random.key_data(jax.random.split(jax.random.key(2), C))

    round_fn = build_fed_tp_round(model, mesh, specs, learning_rate=1e-3)
    new_stacked, stats = round_fn(stacked, frozen, batches, rngs)
    jax.block_until_ready(new_stacked)

    assert np.asarray(stats).shape == (C, 3)
    host = jax.device_get(new_stacked)
    for leaf, leaf0 in zip(jax.tree.leaves(host),
                           jax.tree.leaves(jax.device_get(stacked))):
        # every client ends the round on the consensus average ...
        for c in range(1, C):
            np.testing.assert_allclose(leaf[c], leaf[0], rtol=1e-5)
        # ... and training moved the adapters
    moved = any(
        not np.allclose(a, b)
        for a, b in zip(jax.tree.leaves(host),
                        jax.tree.leaves(jax.device_get(stacked))))
    assert moved


def test_fed_tp_round_mask_freezes_client():
    """Masked-out clients keep their own adapters (the old demo mean had no
    mask at all — this pins the parity with the 1-D programs)."""
    C, TP = 4, 2
    mesh = fed_tp_mesh(C, TP)
    model = build("tiny-llama", num_labels=2)
    B, S = 2, 16
    ids = jnp.ones((B, S), jnp.int32)
    frozen = model.init(jax.random.key(0), ids, ids)["params"]
    from jax.sharding import NamedSharding

    frozen = jax.device_put(
        frozen, jax.tree.map(lambda s: NamedSharding(mesh, s),
                             tp_specs(frozen, axis="tp")))
    adapters = lora_lib.init_lora(jax.random.key(1), frozen, rank=2,
                                  targets=LORA_TARGETS)
    stacked = stack_adapters(mesh, adapters, C)
    rng = np.random.default_rng(0)
    batches = {
        "ids": jnp.asarray(rng.integers(0, 256, (C, 1, B, S)), jnp.int32),
        "mask": jnp.ones((C, 1, B, S), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, 2, (C, 1, B)), jnp.int32),
        "example_mask": jnp.ones((C, 1, B), jnp.float32),
    }
    rngs = jax.random.key_data(jax.random.split(jax.random.key(2), C))
    round_fn = build_fed_tp_round(model, mesh, learning_rate=1e-3)
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0], jnp.float32)
    new_stacked, _ = round_fn(stacked, frozen, batches, rngs, mask=mask)
    host = jax.device_get(new_stacked)
    for leaf in jax.tree.leaves(host):
        # participating clients end on the shared consensus ...
        np.testing.assert_allclose(leaf[0], leaf[1], rtol=1e-5)
        np.testing.assert_allclose(leaf[0], leaf[3], rtol=1e-5)
    # ... while the masked client keeps its OWN locally-trained adapters
    # (1-D semantics: masked = excluded from the mean, not frozen)
    assert any(
        not np.allclose(leaf[2], leaf[0], rtol=1e-6)
        for leaf in jax.tree.leaves(host))


def _tp_cfg(**kw):
    base = dict(
        dataset="synthetic", num_labels=2, seq_len=16, batch_size=4,
        vocab_size=512, model="tiny-llama", lora_rank=2, tp=2,
        num_clients=4, num_rounds=2, learning_rate=1e-3, max_local_batches=2,
        partition=PartitionConfig(kind="iid", iid_samples=16),
    )
    base.update(kw)
    return FedConfig(**base)


def test_engine_fed_tp_server_round():
    """VERDICT r03 #3: a 4-client x tp=2 llama-LoRA round through
    run(config) on the 8-device CPU mesh — tp wired into engine/config."""
    eng = FedEngine(_tp_cfg(mode="server"))
    assert eng.mesh.tp == 2
    assert eng.mesh.mesh.shape == {"clients": 4, "tp": 2}
    # the frozen base really is tensor-sharded over the tp axis
    specs = {
        leaf.sharding.spec
        for leaf in jax.tree.leaves(eng.frozen)
        if hasattr(leaf, "sharding")
    }
    assert any("tp" in str(s) for s in specs), specs
    res = eng.run()
    accs = res.metrics.global_accuracies
    assert len(accs) == 2
    assert np.isfinite(res.metrics.rounds[-1].train_loss)
    assert all(len(r.local_acc) == 4 for r in res.metrics.rounds)


def test_engine_fed_tp_serverless_fused_and_ledger():
    """tp composes with the rest of the product surface: fused gossip rounds
    and the ledger split-phase flow both run on the clients x tp mesh."""
    res = FedEngine(_tp_cfg(mode="serverless", rounds_per_dispatch=2,
                            eval_every=2)).run()
    assert len(res.metrics.rounds) == 2
    res = FedEngine(_tp_cfg(mode="server", num_rounds=1,
                            ledger=LedgerConfig(enabled=True))).run()
    assert res.metrics.rounds[-1].auth == [1.0] * 4
    assert res.metrics.ledger["chain_ok"] == 1.0


def test_tp_requires_lora():
    with pytest.raises(ValueError, match="lora_rank"):
        _tp_cfg(lora_rank=0)


def test_distributed_init_requires_process_id(monkeypatch):
    monkeypatch.setenv("BCFL_NUM_PROCESSES", "2")
    monkeypatch.delenv("BCFL_PROCESS_ID", raising=False)
    with pytest.raises(ValueError, match="process_id"):
        distributed_init()
