"""Bring-up contracts that need no chip: where the compile cache goes, that
``chip_smoke.py`` refuses a CPU unless told otherwise, and that the dist
launcher gives every TPU peer a chip of its own or refuses. (The run of the
whole file under its plumbing switch is tests/test_smoke_plumbing.py.)"""

import json
import os
import subprocess
import sys

import pytest

from bcfl_tpu.config import DistConfig, FedConfig
from bcfl_tpu.core import hostenv
from bcfl_tpu.dist import harness

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_RESOLVE = ("import json, jax; from bcfl_tpu.core.hostenv import "
            "compile_cache; d, from_env = compile_cache(); print(json.dumps("
            "[d, from_env, jax.config.jax_compilation_cache_dir]))")


def _resolve_in_fresh_process():
    env = {k: v for k, v in os.environ.items() if k != hostenv.CACHE_ENV}
    out = subprocess.run([sys.executable, "-c", _RESOLVE], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.splitlines()[-1])


def test_compile_cache_env_wins_and_nothing_is_set_in_code(
        tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the resolver touches neither the
    environment nor jax's config — jax reads the variable itself."""
    import jax

    monkeypatch.setenv(hostenv.CACHE_ENV, str(tmp_path))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **kw: updates.append(a))
    assert hostenv.compile_cache() == (str(tmp_path), True)
    assert updates == [] and os.environ[hostenv.CACHE_ENV] == str(tmp_path)


def test_compile_cache_default_is_one_fixed_path_under_the_checkout():
    """Unset, two fresh processes resolve the SAME path inside the checkout
    (the path is part of every cache key: a directory that moves never
    hits), and it reaches jax's config."""
    a = _resolve_in_fresh_process()
    b = _resolve_in_fresh_process()
    assert a == b == [os.path.join(REPO, ".jax_cache"), False,
                      os.path.join(REPO, ".jax_cache")]


def test_chip_smoke_refuses_a_cpu_backend_before_any_work():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "not a TPU" in out.stderr
    # no result line, and no leg started
    assert "{" not in out.stdout and "leg " not in out.stdout


def _dist_cfg(peers):
    return FedConfig(runtime="dist", mode="server", sync="async",
                     num_clients=2 * peers, eval_every=0,
                     dist=DistConfig(peers=peers))


def test_dist_launcher_refuses_more_tpu_peers_than_chips(
        tmp_path, monkeypatch):
    """On platform tpu a fleet larger than the chip count is refused at
    launch — nothing spawned, no CPU fallback for the losers. The chip
    count is injected, so no chip is needed."""
    monkeypatch.setattr(harness, "spawn_peer", lambda *a, **kw: pytest.fail(
        "a peer was spawned"))
    with pytest.raises(ValueError, match="3 TPU chips.*this host has 2"):
        harness.run_dist(_dist_cfg(3), str(tmp_path / "run"),
                         platform="tpu", tpu_chips=2)
    assert not (tmp_path / "run").exists()


def test_dist_launcher_refuses_a_supervisor_that_holds_the_chips(tmp_path):
    """This pytest process has initialized a jax backend: on a TPU it would
    be holding every chip its peers need."""
    import jax

    jax.devices()
    with pytest.raises(RuntimeError, match="one process owns a chip"):
        harness.run_dist(_dist_cfg(2), str(tmp_path / "run"),
                         platform="tpu", tpu_chips=4)


def test_tpu_peers_are_pinned_one_chip_each():
    """Peer p's environment shows it chip p and nothing else; off-TPU the
    chip-visibility variables are left alone."""
    envs = [harness._peer_env("tpu", chip=p) for p in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_PROCESS_BOUNDS"] == "1,1,1"
               and e["JAX_PLATFORMS"] == "tpu" for e in envs)
    assert "TPU_VISIBLE_CHIPS" not in harness._peer_env("cpu")


def test_unknown_device_kind_is_an_error_not_a_default():
    assert hostenv.device_peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="not in the peak table"):
        hostenv.device_peak_flops("TPU v99")
