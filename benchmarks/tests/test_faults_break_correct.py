"""``correct`` comes out false when the timed path is broken underneath. The
harness's look for a chip is skipped (``run_cell`` is what follows it); the
rest of a run is driven as it stands, once for each fault the cells can
have: a step that returns its state unchanged, half of the batch left out
with the mean taken over the rest, and a client's update left out of the
aggregate; and for the guarded cell a gate that masks nobody, which the
plain recomputation of the mask catches. (The exchange between chips exists
only in the four-chip cell, which is not in BENCHMARK.json yet.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness

SEED = 2147483659


def state_unchanged(engine):
    p = engine.progs

    def fused(carry, *a, **k):
        keep = jax.tree.map(jnp.copy, carry)  # the call donates its input
        _, aux = p.server_rounds_static_fp(carry, *a, **k)
        return keep, aux

    def collapse(stacked, w, fallback):
        return fallback

    engine.progs = dataclasses.replace(p, server_rounds_static_fp=fused, collapse=collapse)


def half_batch(engine):
    batches, n_ex = engine._static_batches
    B = batches["example_mask"].shape[-1]
    half = (jnp.arange(B) < B // 2).astype(jnp.float32)
    engine._static_batches = (dict(batches, example_mask=batches["example_mask"] * half), n_ex)


def client_left_out(engine):
    batches, n_ex = engine._static_batches
    n_ex = np.array(n_ex)
    live = [c for c, m in enumerate(engine._participation(0)["mask"]) if m > 0]
    n_ex[live[-1]] = 0.0  # its weight in the example-weighted mean
    engine._static_batches = (batches, n_ex)


def gate_left_out(engine):
    n = engine.C
    engine._participation = lambda rnd, components=None: {
        "anomalies": [], "mask": np.ones((n,), np.float32), "scores": np.zeros((n,))}


FAULTS = [state_unchanged, half_batch, client_left_out]


@pytest.mark.parametrize("cell_name", ["bert-base.fedavg-s128", "albert-base.guarded-s128"])
@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_fault_is_not_correct(cell_name, fault, tmp_path):
    res = harness.run_cell(cell_name, SEED, 1.0, False, plumbing=True,
                           out_dir=str(tmp_path), prepare=fault)
    assert res["correct"] is False
    bad = [k for k, v in res["compared"].items() if not v["ok"]]
    assert bad and all(k.startswith(("loss_", "dparam_", "turn_")) for k in bad)


def test_gate_left_out_is_not_correct(tmp_path):
    res = harness.run_cell("albert-base.guarded-s128", SEED, 1.0, False, plumbing=True,
                           out_dir=str(tmp_path), prepare=gate_left_out)
    assert res["correct"] is False
    assert [k for k, v in res["compared"].items() if not v["ok"]] == ["mask_mismatch_rounds"]


@pytest.mark.parametrize("cell_name", ["bert-base.fedavg-s128", "albert-base.guarded-s128"])
def test_sound_run_is_correct(cell_name, tmp_path):
    res = harness.run_cell(cell_name, SEED, 1.0, False, plumbing=True, out_dir=str(tmp_path))
    assert res["correct"] is True and res["failed"] == 0
