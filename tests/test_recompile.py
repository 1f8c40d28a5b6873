"""Regression: the engine's round programs must compile exactly once.

The r04 bench recorded 87.5 s/dispatch because the warmup call's input
params were single-device committed while its output carried the program's
``out_shardings`` — so the SECOND call was a new jit cache entry (a full
recompile) that landed inside the timed loop (PERF.md, results/
dispatch_bisect.json). ``FedEngine.__init__`` now pins ``trainable0`` /
``frozen`` to their steady-state shardings; this test pins THAT by counting
jit cache entries after a multi-round run. A second cache entry on any round
program is this bug come back (on a chip it costs minutes per
round-2 dispatch).
"""

import pytest

from bcfl_tpu.config import FedConfig, PartitionConfig
from bcfl_tpu.fed.engine import FedEngine

pytestmark = pytest.mark.slow  # engine-suite tier: compile-heavy on the
# 8-device CPU mesh; the tier-1 'not slow' window runs the chaos matrix
# (tests/test_faults.py) as its fast engine coverage instead


@pytest.fixture(autouse=True)
def _fresh_programs(monkeypatch):
    """These tests count jit cache entries PER ENGINE; the cross-engine
    program cache deliberately accumulates one entry per tree structure on
    shared objects (e.g. lora adapters after full params), which is correct
    behavior but not what this regression pins. Disable sharing here."""
    monkeypatch.setenv("BCFL_PROGRAM_CACHE", "0")


def _run(mode, **kw):
    cfg = FedConfig(
        name=f"recompile_{mode}", model="tiny-bert", dataset="synthetic",
        mode=mode, num_clients=4, num_rounds=3, seq_len=16, batch_size=4,
        max_local_batches=2,
        partition=PartitionConfig(kind="iid", iid_samples=8,
                                  resample_each_round=True),
        **kw,
    )
    eng = FedEngine(cfg)
    res = eng.run()
    assert len(res.metrics.rounds) == 3
    return eng


@pytest.mark.parametrize("mode", ["server", "serverless"])
def test_round_programs_compile_once(mode):
    eng = _run(mode)
    progs = eng.progs
    # the mode's primary round program MUST have compiled exactly once —
    # == 1, not <= 1, so the test cannot pass vacuously if a future engine
    # routes rounds elsewhere (then update this map: it pins the hot path)
    hot = "server_round" if mode == "server" else "gossip_round"
    assert getattr(progs, hot)._cache_size() == 1, hot
    for name in ("server_round", "server_rounds", "server_rounds_static",
                 "gossip_round", "gossip_rounds", "gossip_rounds_static",
                 "eval_clients", "eval_clients_global", "eval_global",
                 "client_updates", "local_updates", "mix_only", "collapse"):
        size = getattr(progs, name)._cache_size()
        # uncalled programs are 0; any program the run used must be 1
        assert size <= 1, f"{name} compiled {size}x across a 3-round run"


def test_lora_round_programs_compile_once():
    eng = _run("server", lora_rank=2)
    size = eng.progs.server_round._cache_size()
    assert size == 1, f"lora server_round compiled {size}x (0 = not the hot path)"


def test_async_round_programs_compile_once():
    """The buffered-async path chains stacked params through
    local_updates -> collapse -> broadcast -> select every round; any
    sharding drift in that chain recompiles local_updates round over round."""
    eng = _run("serverless", sync="async", async_buffer=2)
    assert eng.progs.local_updates._cache_size() == 1
    assert eng.progs.collapse._cache_size() <= 1
