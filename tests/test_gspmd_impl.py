"""Numeric parity between the two implementations of the federated programs:

- ``impl="shard_map"`` — explicit psum/ppermute manual SPMD
  (:mod:`bcfl_tpu.parallel.collectives`),
- ``impl="gspmd"``     — global-array math under jit + sharding annotations
  (:mod:`bcfl_tpu.parallel.gspmd`), the default since it was ~200x faster
  in the one on-chip bisection on record (PERF.md "Earlier recordings").

Run on the 8-device CPU mesh so the GSPMD partitioner actually shards the
client dim and inserts real collectives, including the 10-clients-on-5-devices
stacked layout.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcfl_tpu.core import client_mesh
from bcfl_tpu.fed import build_programs
from bcfl_tpu.fed.synthetic import synthetic_round_inputs
from bcfl_tpu.models import build
from bcfl_tpu.parallel import collectives, gspmd

pytestmark = pytest.mark.slow  # engine-suite tier: compile-heavy on the
# 8-device CPU mesh; the tier-1 'not slow' window runs the chaos matrix
# (tests/test_faults.py) as its fast engine coverage instead


def _setup(num_clients, gossip_steps=1, seq=16, batch=4, steps=2):
    model = build("tiny-bert", num_labels=2, vocab_size=512)
    mesh = client_mesh(num_clients)
    kwargs = dict(learning_rate=3e-4, gossip_steps=gossip_steps)
    sm = build_programs(model, mesh, impl="shard_map", **kwargs)
    gs = build_programs(model, mesh, impl="gspmd", **kwargs)
    ids = jnp.ones((batch, seq), jnp.int32)
    params = model.init(jax.random.key(1), ids, ids)["params"]
    batches, weights, rngs = synthetic_round_inputs(
        mesh, steps=steps, batch=batch, seq=seq, vocab_size=512)
    return mesh, sm, gs, params, batches, weights, rngs


def _max_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(np.abs(np.asarray(x) - np.asarray(y)).max()), a, b)))


@pytest.mark.parametrize("num_clients", [8, 10])
def test_server_round_parity(num_clients):
    mesh, sm, gs, params, batches, weights, rngs = _setup(num_clients)
    p1, s1 = sm.server_round(params, None, batches, weights, rngs)
    p2, s2 = gs.server_round(params, None, batches, weights, rngs)
    assert _max_diff(p1, p2) < 1e-5
    assert _max_diff(s1, s2) < 1e-3


def test_server_round_all_masked_keeps_start():
    mesh, sm, gs, params, batches, weights, rngs = _setup(8)
    zero = jnp.zeros_like(weights)
    p2, _ = gs.server_round(params, None, batches, zero, rngs)
    assert _max_diff(p2, params) == 0.0


@pytest.mark.parametrize("gossip_steps", [0, 1])
@pytest.mark.parametrize("num_clients", [8, 10])
def test_gossip_round_parity(gossip_steps, num_clients):
    # 10-on-5 covers the stacked per_device=2 layout: a ring-order divergence
    # between gspmd.ring_shift (global roll) and collectives.ring_shift
    # (local roll + boundary ppermute) would silently change gossip topology
    mesh, sm, gs, params, batches, weights, rngs = _setup(
        num_clients, gossip_steps=gossip_steps)
    # mask one client out: exercises the freeze + neighbor-mask paths
    mask = weights.at[3].set(0.0)
    stacked = sm.broadcast(params)
    p1, s1 = sm.gossip_round(stacked, None, batches, mask, rngs)
    p2, s2 = gs.gossip_round(gs.broadcast(params), None, batches, mask, rngs)
    assert _max_diff(p1, p2) < 1e-5
    assert _max_diff(s1, s2) < 1e-3


@pytest.mark.parametrize("num_clients", [8, 10])
def test_gossip_rounds_parity(num_clients):
    """The fused multi-round gossip program (R rounds scanned on-device)
    agrees across impls and with R sequential gossip_round calls."""
    R = 2
    mesh, sm, gs, params, batches, weights, rngs = _setup(num_clients)
    mask = weights.at[3].set(0.0)
    rb = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), batches)
    rm = jnp.broadcast_to(mask[None], (R,) + mask.shape)
    rr = jnp.stack([rngs, jax.vmap(jax.random.fold_in)(
        rngs, jnp.full((rngs.shape[0],), 7, jnp.uint32))])

    p1, s1 = sm.gossip_rounds(sm.broadcast(params), None, rb, rm, rr)
    p2, s2 = gs.gossip_rounds(gs.broadcast(params), None, rb, rm, rr)
    assert _max_diff(p1, p2) < 1e-5
    assert _max_diff(s1, s2) < 1e-3

    # sequential oracle: R gossip_round calls
    seq = gs.broadcast(params)
    for i in range(R):
        seq, _ = gs.gossip_round(
            seq, None, jax.tree.map(lambda x: x[i], rb), rm[i], rr[i])
    assert _max_diff(p2, seq) < 1e-5

    # static variant (one batch tree reused every round), both impls
    p3, s3 = gs.gossip_rounds_static(
        gs.broadcast(params), None, batches, rm, rr)
    assert _max_diff(p2, p3) < 1e-5
    assert _max_diff(s2, s3) < 1e-3
    p4, s4 = sm.gossip_rounds_static(
        sm.broadcast(params), None, batches, rm, rr)
    assert _max_diff(p3, p4) < 1e-5
    assert _max_diff(s3, s4) < 1e-3


def test_split_phase_parity():
    mesh, sm, gs, params, batches, weights, rngs = _setup(8)
    u1, s1 = sm.client_updates(params, None, batches, rngs)
    u2, s2 = gs.client_updates(params, None, batches, rngs)
    assert _max_diff(u1, u2) < 1e-5

    mask = weights.at[0].set(0.0)
    m1 = sm.mix_only(u1, mask, sm.broadcast(params))
    m2 = gs.mix_only(u2, mask, gs.broadcast(params))
    assert _max_diff(m1, m2) < 1e-5

    c1 = sm.collapse(u1, mask, params)
    c2 = gs.collapse(u2, mask, params)
    assert _max_diff(c1, c2) < 1e-5


def test_eval_parity():
    mesh, sm, gs, params, batches, weights, rngs = _setup(8)
    ev = {"ids": batches["ids"], "mask": batches["mask"],
          "labels": batches["labels"], "example_mask": batches["example_mask"]}
    e1 = sm.eval_clients_global(params, None, ev)
    e2 = gs.eval_clients_global(params, None, ev)
    assert _max_diff(e1, e2) < 1e-3


def test_collective_helpers_parity():
    """The raw collective twins agree leaf-for-leaf on a stacked tree."""
    C = 8
    key = jax.random.key(0)
    tree = {"a": jax.random.normal(key, (C, 5, 3)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (C,))}
    mask = jnp.asarray([1, 1, 0, 1, 1, 1, 0, 1], jnp.float32)

    mesh = client_mesh(C)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    sm_mean = jax.jit(shard_map(
        lambda t, w: collectives.masked_weighted_mean(t, w, mesh.axis),
        mesh=mesh.mesh, in_specs=(P(mesh.axis), P(mesh.axis)),
        out_specs=P(), check_vma=False))
    assert _max_diff(sm_mean(tree, mask),
                     gspmd.masked_weighted_mean(tree, mask)) < 1e-6

    for direction in (+1, -1):
        sm_shift = jax.jit(shard_map(
            lambda t: collectives.ring_shift(t, mesh.axis, direction),
            mesh=mesh.mesh, in_specs=(P(mesh.axis),),
            out_specs=P(mesh.axis), check_vma=False))
        assert _max_diff(sm_shift(tree), gspmd.ring_shift(tree, direction)) == 0.0

    sm_gossip = jax.jit(shard_map(
        lambda t, m: collectives.gossip_mix(t, m, 0.5, mesh.axis, steps=2),
        mesh=mesh.mesh, in_specs=(P(mesh.axis), P(mesh.axis)),
        out_specs=P(mesh.axis), check_vma=False))
    assert _max_diff(sm_gossip(tree, mask),
                     gspmd.gossip_mix(tree, mask, 0.5, steps=2)) < 1e-6

    W = jax.random.uniform(jax.random.fold_in(key, 2), (C, C))
    W = W / W.sum(1, keepdims=True)
    sm_mix = jax.jit(shard_map(
        lambda t: collectives.mix_with_matrix(t, W, mesh.axis, mesh.per_device),
        mesh=mesh.mesh, in_specs=(P(mesh.axis),),
        out_specs=P(mesh.axis), check_vma=False))
    assert _max_diff(sm_mix(tree), gspmd.mix_with_matrix(tree, W)) < 1e-5
