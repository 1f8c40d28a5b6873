"""A dispatch's client keys from one cached program (PERF.md section 6,
PR 26): ``core.prng.client_key_data`` against the eager definition
``client_round_keys`` bit for bit, and the engine's use of it: one call a
fused dispatch and one a per-round round, nothing compiled after the first,
counted on the ``round_program/inputs`` span as ``key_programs``."""

import json
import os

import numpy as np
import pytest

import jax

from bcfl_tpu.config import FedConfig, LedgerConfig, PartitionConfig
from bcfl_tpu.core import client_key_data, client_round_keys
from bcfl_tpu.fed.engine import FedEngine
from bcfl_tpu.metrics import StepClock

IMPLS = ["threefry2x32", "rbg", "unsafe_rbg"]
HIGH_ROUND = 2 ** 16 + 5


def _eager(key, clients, rnd):
    return np.asarray(jax.random.key_data(client_round_keys(key, clients, rnd)))


# ------------------------------------------------------------ (a) the program

@pytest.mark.parametrize("rnd", [0, HIGH_ROUND])
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("clients", ["count", "ids"])
@pytest.mark.parametrize("impl", IMPLS)
def test_chunk_keys_equal_the_eager_definition(impl, clients, k, rnd):
    key = jax.random.fold_in(jax.random.key(11, impl=impl), 4)
    C = 6
    rounds = np.arange(rnd, rnd + k, dtype=np.int32)
    if clients == "count":
        ids = np.tile(np.arange(C, dtype=np.int32), (k, 1))
        want = [_eager(key, C, int(r)) for r in rounds]
    else:  # a cohort's registry ids, another cohort each round
        ids = (np.array([5, 900, 3, 77, 12, 100_000], np.int32)[None]
               + 7 * np.arange(k, dtype=np.int32)[:, None])
        want = [_eager(key, ids[i], int(r)) for i, r in enumerate(rounds)]
    got = np.asarray(client_key_data(key, ids, rounds))
    assert got.dtype == np.uint32 and got.shape == (k, C, want[0].shape[-1])
    np.testing.assert_array_equal(got, np.stack(want))
    # a scalar round with one id row: the same rows, the leading axis dropped
    np.testing.assert_array_equal(
        np.asarray(client_key_data(key, ids[0], rounds[0])), want[0])


@pytest.mark.parametrize("impl", IMPLS)
def test_rounds_and_ids_are_operands_not_constants(impl):
    key = jax.random.key(3, impl=impl)
    ids = np.tile(np.arange(4, dtype=np.int32), (2, 1))
    client_key_data(key, ids, np.array([0, 1], np.int32))
    size = client_key_data._cache_size()
    for rnd in (2, 40, HIGH_ROUND):
        client_key_data(key, ids + rnd, np.array([rnd, rnd + 1], np.int32))
    assert client_key_data._cache_size() == size


def test_clock_count_lands_on_the_innermost_open_span():
    clock = StepClock()
    clock.count("key_programs")  # no span open: nothing is counted
    for _ in range(2):
        with clock.phase("round_program"):
            with clock.span("inputs") as counts:
                clock.count("key_programs")
                counts["h2d_bytes"] = 8
            with clock.span("enqueue"):
                pass
    kids = clock.summary()["round_program"]["children"]
    assert kids["inputs"]["key_programs"] == 2
    assert kids["inputs"]["h2d_bytes"] == 16
    assert "key_programs" not in kids["enqueue"]


# ------------------------------------------------------------- (b) the engine

def _tiny(**kw):
    base = dict(
        dataset="synthetic", model="tiny-bert", num_clients=4, num_rounds=4,
        seq_len=16, batch_size=4, max_local_batches=2, eval_every=0,
        mode="server", ledger=LedgerConfig(enabled=True),
        partition=PartitionConfig(kind="iid", iid_samples=8),
    )
    base.update(kw)
    return FedConfig(**base)


@pytest.mark.parametrize("kw,dispatches", [
    ({"rounds_per_dispatch": 2}, 2),
    ({}, 4),
    ({"mode": "serverless", "ledger": LedgerConfig()}, 4),
], ids=["fused", "per_round", "serverless"])
def test_one_key_program_a_dispatch_and_none_compiled_after_the_first(
        kw, dispatches, tmp_path):
    tdir = str(tmp_path / "tel")
    eng = FedEngine(_tiny(telemetry_dir=tdir, **kw))
    sizes = []
    res = eng.run(on_round=lambda rec: sizes.append(
        client_key_data._cache_size()))
    assert len(sizes) == 4 and len(set(sizes)) == 1, sizes
    inputs = res.metrics.phases["round_program"]["children"]["inputs"]
    assert inputs["key_programs"] == dispatches
    events = [json.loads(x)
              for x in open(os.path.join(tdir, "events_engine.jsonl"))]
    per_span = [e["key_programs"] for e in events if e["ev"] == "phase"
                and e["name"] == "round_program/inputs"
                and "key_programs" in e]
    assert per_span == [1] * dispatches


@pytest.mark.parametrize("impl", [None, "rbg", "unsafe_rbg"])
def test_rngs_is_a_row_of_the_chunk_and_the_eager_keys(impl):
    eng = FedEngine(_tiny(prng_impl=impl))
    _, _, rrngs, _ = eng._chunk_inputs(0, 3)
    chunk = np.asarray(rrngs)
    assert rrngs.sharding.spec == jax.sharding.PartitionSpec(None, "clients")
    lane = jax.random.fold_in(eng.root_key, 4)
    for r in range(3):
        rngs = eng._rngs(r)
        assert rngs.sharding.spec == jax.sharding.PartitionSpec("clients")
        np.testing.assert_array_equal(np.asarray(rngs), chunk[r])
        np.testing.assert_array_equal(chunk[r], _eager(lane, eng.C, r))


def test_cohort_keys_follow_the_registry_ids():
    eng = FedEngine(_tiny(registry_size=64, sample_clients=4,
                          ledger=LedgerConfig()))
    lane = jax.random.fold_in(eng.root_key, 4)
    for r in (0, 1):
        ids = eng._cohort_ids(r)
        assert ids is not None and len(ids) == eng.C
        np.testing.assert_array_equal(
            np.asarray(eng._rngs(r)), _eager(lane, ids, r))
