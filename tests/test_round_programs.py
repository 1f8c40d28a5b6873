"""The round programs are ONE round step (``fed/client_step.py::_round_step``),
jitted alone (``server_round``, ``gossip_round``) or scanned (the eight fused
``*_rounds*`` programs). These pin that in tier-1: a fused program equals its
per-round program called round after round, and an all-masked round combines
nothing.

tiny-bert, 4 clients on 4 of the 8 CPU devices, 2 rounds, one client masked
out so the freeze and neighbour-mask paths run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcfl_tpu.core import client_mesh
from bcfl_tpu.fed import build_programs
from bcfl_tpu.fed.synthetic import synthetic_round_inputs
from bcfl_tpu.models import build

C, R = 4, 2


@pytest.fixture(scope="module")
def setup():
    model = build("tiny-bert", num_labels=2, vocab_size=512)
    mesh = client_mesh(C)
    progs = build_programs(model, mesh, learning_rate=3e-4)
    batches, weights, rngs = synthetic_round_inputs(
        mesh, steps=2, batch=4, seq=16, vocab_size=512)
    params = model.init(jax.random.key(1), batches["ids"][0, 0],
                        batches["mask"][0, 0])["params"]
    # another round, other keys
    rr = jnp.stack([rngs, jax.vmap(jax.random.fold_in)(
        rngs, jnp.full((C,), 7, jnp.uint32))])
    return progs, params, batches, weights, rr


def _close(a, b, atol):
    for x, y in zip(jax.tree.leaves(jax.device_get(a)),
                    jax.tree.leaves(jax.device_get(b)), strict=True):
        np.testing.assert_allclose(x, y, atol=atol)


@pytest.mark.parametrize("with_fp", [False, True], ids=["plain", "fp"])
@pytest.mark.parametrize("static", [False, True], ids=["stacked", "static"])
@pytest.mark.parametrize("mode", ["server", "gossip"])
def test_fused_program_equals_its_per_round_program(setup, mode, static, with_fp):
    progs, params, batches, weights, rr = setup
    start = params if mode == "server" else progs.broadcast(params)
    mask = weights.at[3].set(0.0)
    rm = jnp.broadcast_to(mask[None], (R, C))
    rb = batches if static else jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), batches)
    name = f"{mode}_rounds" + "_static" * static + "_fp" * with_fp
    extra = (jnp.zeros((R, C), jnp.float32),) if with_fp else ()
    fused, out = getattr(progs, name)(start, None, rb, rm, rr, *extra)

    one_round = getattr(progs, f"{mode}_round")
    seq, seq_stats = start, []
    for i in range(R):
        seq, s = one_round(seq, None, batches, mask, rr[i])
        seq_stats.append(s)
    # a scan may fuse differently: the tolerance tests/test_engine.py holds
    # the fused ledger run to
    _close(fused, seq, atol=1e-5)
    stats = out[0] if with_fp else out
    _close(stats, jnp.stack(seq_stats), atol=1e-3)
    if with_fp:
        _, fp_commit, fp_recv, auth = out
        # clean transport is an exact float identity: what was committed is
        # what arrived, lane for lane, masked-out client included
        assert fp_commit.shape[:2] == (R, C)
        np.testing.assert_array_equal(np.asarray(fp_commit), np.asarray(fp_recv))
        np.testing.assert_array_equal(np.asarray(auth), np.ones((R, C), np.float32))


@pytest.mark.parametrize("mode", ["server", "gossip"])
def test_all_masked_round_combines_nothing(setup, mode):
    """Server: the aggregate of nobody is the round's starting parameters,
    bit for bit. Gossip: every client is frozen on its own post-train state
    (what ``local_updates`` returns); nothing diffuses."""
    progs, params, batches, weights, rr = setup
    zero = jnp.zeros_like(weights)
    if mode == "server":
        got, _ = progs.server_round(params, None, batches, zero, rr[0])
        _close(got, params, atol=0.0)
    else:
        stacked = progs.broadcast(params)
        got, _ = progs.gossip_round(stacked, None, batches, zero, rr[0])
        own, _ = progs.local_updates(stacked, None, batches, rr[0])
        _close(got, own, atol=1e-6)
