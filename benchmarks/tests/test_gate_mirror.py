"""The plain recomputation of the participation gate (reference/gate.py,
which imports nothing of the program) gives the mask the program's own
gate gives, seed by seed, large seeds included."""

import json
import os

import numpy as np
import pytest

from bcfl_tpu.topology import anomaly_filter
from bcfl_tpu.topology.graph import random_graph

from benchmarks import harness
from benchmarks.reference import gate

GATE = harness.load_json("workloads", "albert-base.guarded-s128.json")["gate"]
SEEDS = list(range(40)) + [2147483659, 2222222223, 3141592653, 2**31 + 999]


@pytest.mark.parametrize("n", [6, 8])
def test_mask_agrees_with_the_program(n):
    low, high = GATE["bandwidth_mbps"]
    kept = set()
    for seed in SEEDS:
        want = anomaly_filter(GATE["filter"], random_graph(n, low, high, seed=seed),
                              protect=(GATE["protected_client"],))["mask"]
        got = gate.expected_mask(GATE, n, seed)
        assert np.array_equal(got, want), seed
        assert got[GATE["protected_client"]] == 1.0
        kept.add(int(got.sum()))
    assert len(kept) > 1  # the mask does differ from seed to seed


def test_no_gate_keeps_everyone():
    assert gate.expected_mask(None, 5, 7).tolist() == [1.0] * 5


def test_only_the_guarded_cell_states_a_gate():
    d = os.path.join(harness.HERE, "workloads")
    for f in os.listdir(d):
        cell = json.load(open(os.path.join(d, f)))
        gated = cell["fed"].get("topology", {}).get("anomaly_filter") is not None
        assert gated == ("gate" in cell), f
        assert cell["limits"]["mask_mismatch_rounds"] == 0
