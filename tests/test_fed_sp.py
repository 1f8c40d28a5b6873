"""Federated clients x sequence parallelism through the PRODUCT path:
``FedConfig(sp=...)`` -> FedEngine builds the 2-D (clients, seq) mesh,
swaps the llama attention for ring attention over the seq axis, and runs
the unchanged GSPMD round programs. The composition the reference cannot
express: many clients x documents longer than one chip's activation memory.
"""

import numpy as np
import pytest

from bcfl_tpu.config import FedConfig, PartitionConfig
from bcfl_tpu.fed.engine import FedEngine

pytestmark = pytest.mark.slow  # engine-suite tier: compile-heavy on the
# 8-device CPU mesh; the tier-1 'not slow' window runs the chaos matrix
# (tests/test_faults.py) as its fast engine coverage instead


def _cfg(**kw):
    base = dict(
        name="fed_sp", model="tiny-llama", dataset="synthetic",
        task="causal_lm", lora_rank=2, mode="server",
        num_clients=2, num_rounds=2, seq_len=32, batch_size=2,
        max_local_batches=2, sp=4,
        partition=PartitionConfig(kind="iid", iid_samples=8))
    base.update(kw)
    return FedConfig(**base)


def test_fed_sp_round_runs_and_learns():
    eng = FedEngine(_cfg())
    assert eng.mesh.mesh.shape == {"clients": 2, "seq": 4}
    assert eng.model.cfg.attention_override is not None
    res = eng.run()
    losses = [r.train_loss for r in res.metrics.rounds]
    assert len(losses) == 2 and np.isfinite(losses).all()
    assert losses[1] < losses[0], losses


def test_fed_sp_serverless_gossip():
    eng = FedEngine(_cfg(mode="serverless"))
    res = eng.run()
    assert np.isfinite([r.train_loss for r in res.metrics.rounds]).all()


def test_sp_encoder_classification():
    """Encoders ride the NON-causal ring: long-document classification
    (the reference's medical-transcriptions shape) with the sequence
    sharded per client."""
    eng = FedEngine(_cfg(model="tiny-bert", task="classification",
                         lora_rank=0, num_rounds=1))
    assert eng.model.cfg.attention_override is not None
    res = eng.run()
    assert np.isfinite(res.metrics.rounds[0].train_loss)


def test_sp_tp_exclusive():
    with pytest.raises(ValueError, match="ONE inner mesh axis"):
        _cfg(tp=2)


def test_sp_async_buffered():
    """Buffered-async aggregation composes with the (clients, seq) mesh:
    local_updates/collapse run the same GSPMD programs over the 2-D mesh."""
    eng = FedEngine(_cfg(mode="serverless", sync="async", async_buffer=1,
                         num_rounds=2))
    res = eng.run()
    assert np.isfinite([r.train_loss for r in res.metrics.rounds]).all()


def test_sp_full_finetune_also_works():
    # unlike tp (frozen-base sharding -> needs LoRA), sp shards only
    # activations: full fine-tune composes
    eng = FedEngine(_cfg(lora_rank=0, num_rounds=1))
    res = eng.run()
    assert np.isfinite(res.metrics.rounds[0].train_loss)
