"""Every cell's file at tiny size on the CPU, with ``--trace 0`` and with
``--trace 1``: the same ``correct`` and the same compared numbers, and a
last line of the contract's shape."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


def run(cell, trace, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    p = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "benchmarks", "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "2", "--trace", str(trace), "--plumbing"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_trace_changes_nothing_that_is_compared(cell):
    r0, err0 = run(cell, 0)
    r1, err1 = run(cell, 1)
    for r in (r0, r1):
        assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
        assert list(r)[-1] == "compared" and r["plumbing_only"] is True
        assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
        assert set(r["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
        for m in r["metrics"].values():
            assert set(m) == {"value", "unit"}
    assert r0["compared"] == r1["compared"]
    assert set(r0["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    # a CPU run reports no device metric
    assert not any(k.startswith(("device.", "step.mfu", "round_program.")) for k in r1["metrics"])
    assert "engine.host_ms_per_round" in r1["metrics"]
    assert "[compared] loss_r0" in err0 and "[compared] loss_r0" in err1
    assert err1.strip().splitlines()[-1].startswith("[compared]")


def test_no_accelerator_means_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "benchmarks", "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=harness.ROOT, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_four_chip_cell_on_four_virtual_devices():
    """The cell that is written but not yet in BENCHMARK.json: its mesh and
    sharding on four CPU devices (on-chip-measurement guide, rehearsal 2)."""
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "benchmarks", "run.py"), "--workload",
         "bert-base.fedavg-s128-x4", "--seed", "5", "--seconds", "1", "--trace", "0", "--plumbing"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
        cwd=harness.ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True and r["device"]["count"] == 4
