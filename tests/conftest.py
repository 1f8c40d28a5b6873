"""Test harness: force an 8-device CPU mesh so every collective
(psum FedAvg, ppermute gossip) is exercised exactly as on a TPU pod —
the distributed-without-hardware strategy from SURVEY.md §4. The
environment variables below are all it takes; they are set before jax is
imported.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
# persistent-cache floor: jax's default 1 s would skip most of this suite's
# many small programs; 0.5 s is what the suite's wall was measured with. An
# environment variable, so peer subprocesses get it too.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

# the checkout under test must always win over any installed copy of the
# package (a stale non-editable `pip install .` would otherwise shadow it)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bcfl_tpu.core.hostenv import compile_cache  # noqa: E402

# persistent XLA compilation cache: the suite's cost is dominated by
# compiles of the engine/round programs, and the in-process program
# memoization (client_step._PROGRAM_CACHE) cannot help across pytest
# processes. JAX_COMPILATION_CACHE_DIR wins when set; otherwise the fixed
# tests/.xla_cache, exported so the dist loopback tests' PEER SUBPROCESSES
# (dist.harness copies os.environ) share it — peers build 1-device meshes,
# so their keys differ from the pytest process's but are identical ACROSS
# dist tests and re-runs, which is where the savings are.
compile_cache(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           ".xla_cache"))

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _reap_dist_peers():
    """Orphan reaper for the dist runtime (RUNTIME.md §7): any peer
    subprocess a dist test spawned and failed to collect — a hung peer, an
    interrupted harness — is SIGKILLed at session teardown, so a straggler
    can never squat on the tier-1 870 s window or outlive the CI job. The
    peers also self-destruct (in-process deadline + parent-death watchdogs);
    this is the belt to those suspenders."""
    yield
    from bcfl_tpu.dist.harness import reap_all

    killed = reap_all()
    if killed:
        print(f"\n[conftest] reaped {killed} straggler dist peer(s)")
