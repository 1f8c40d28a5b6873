"""The compiled federated round: training happens, FedAvg/gossip aggregate,
masks gate contributions — all inside one jitted program on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np

from bcfl_tpu.core import client_mesh, client_round_keys
from bcfl_tpu.config import PartitionConfig
from bcfl_tpu.data import (
    HashTokenizer, Partitioner, TokenCache, client_batches, load_dataset,
)
from bcfl_tpu.fed import build_programs
from bcfl_tpu.models import build, lora as lora_lib

import pytest

pytestmark = pytest.mark.slow  # engine-suite tier: compile-heavy on the
# 8-device CPU mesh; the tier-1 'not slow' window runs the chaos matrix
# (tests/test_faults.py) as its fast engine coverage instead


def _setup(num_clients=8, num_labels=2, samples=64, batch=16, seq=32):
    ds = load_dataset("synthetic", num_labels=num_labels, n_train=1024, n_test=256)
    tok = HashTokenizer(512)
    cache = TokenCache.build(ds, tok, seq_len=seq)
    part = Partitioner(
        PartitionConfig(kind="iid", iid_samples=samples), ds.n_train, ds.n_test,
        jax.random.key(0),
    )
    model = build("tiny-bert", num_labels=num_labels, vocab_size=512)
    mesh = client_mesh(num_clients)
    progs = build_programs(model, mesh, learning_rate=3e-4)
    ids = jnp.ones((batch, seq), jnp.int32)
    variables = model.init(jax.random.key(1), ids, ids)
    return ds, cache, part, model, mesh, progs, variables["params"]


def _round_inputs(cache, part, mesh, rnd, batch=16, steps=4):
    tree, n_ex = client_batches(cache, part, mesh.num_clients, rnd, batch, max_batches=steps)
    tree = mesh.shard_clients(jax.tree.map(jnp.asarray, tree))
    keys = client_round_keys(jax.random.key(42), mesh.num_clients, rnd)
    rngs = mesh.shard_clients(jax.random.key_data(keys))
    return tree, n_ex, rngs


def test_server_round_trains_and_aggregates():
    ds, cache, part, model, mesh, progs, params = _setup()
    weights = mesh.shard_clients(jnp.ones((mesh.num_clients,)))

    batches, n_ex, rngs = _round_inputs(cache, part, mesh, 0)
    new_params, stats = progs.server_round(params, None, batches, weights, rngs)
    stats = np.asarray(stats)  # [C, 3] = loss*n, correct, n
    assert stats.shape == (8, 3)
    assert (stats[:, 2] > 0).all()
    # aggregated params differ from the start and are replicated
    diff = jax.tree.leaves(
        jax.tree.map(lambda a, b: np.abs(np.asarray(a - b)).max(), new_params, params)
    )
    assert max(diff) > 0

    # a second round from the aggregate trains further and loss drops
    losses = []
    p = params
    for rnd in range(3):
        batches, n_ex, rngs = _round_inputs(cache, part, mesh, rnd)
        p, stats = progs.server_round(p, None, batches,
                                      mesh.shard_clients(jnp.asarray(n_ex)), rngs)
        stats = np.asarray(stats).sum(0)
        losses.append(stats[0] / stats[2])
    assert losses[-1] < losses[0]


def test_server_round_mask_excludes_client():
    """A masked client's (poisoned) update must not touch the aggregate."""
    ds, cache, part, model, mesh, progs, params = _setup()
    batches, n_ex, rngs = _round_inputs(cache, part, mesh, 0)

    # poison client 5's labels to a constant wrong value
    poisoned = jax.tree.map(lambda x: np.asarray(x).copy(), jax.device_get(batches))
    poisoned["labels"][5] = 0
    poisoned["ids"][5] = 7
    poisoned = mesh.shard_clients(jax.tree.map(jnp.asarray, poisoned))

    w_all = jnp.ones((8,))
    w_masked = w_all.at[5].set(0.0)

    clean_agg, _ = progs.server_round(params, None, batches,
                                      mesh.shard_clients(w_masked), rngs)
    pois_agg, _ = progs.server_round(params, None, poisoned,
                                     mesh.shard_clients(w_masked), rngs)
    # with client 5 masked, poisoning client 5 changes nothing
    diffs = jax.tree.leaves(jax.tree.map(
        lambda a, b: np.abs(np.asarray(a - b)).max(), clean_agg, pois_agg))
    assert max(diffs) < 1e-6

    pois_unmasked, _ = progs.server_round(params, None, poisoned,
                                          mesh.shard_clients(w_all), rngs)
    diffs = jax.tree.leaves(jax.tree.map(
        lambda a, b: np.abs(np.asarray(a - b)).max(), clean_agg, pois_unmasked))
    assert max(diffs) > 1e-6  # sanity: unmasked poison does leak


def test_gossip_round_mixes_neighbors():
    ds, cache, part, model, mesh, progs, params = _setup()
    client_params = progs.broadcast(params)
    mask = mesh.shard_clients(jnp.ones((8,)))
    batches, n_ex, rngs = _round_inputs(cache, part, mesh, 0)
    new_cp, stats = progs.gossip_round(client_params, None, batches, mask, rngs)
    # per-client params now differ across clients (local data differs)...
    leaf = np.asarray(jax.tree.leaves(new_cp)[0])
    assert leaf.shape[0] == 8
    assert np.abs(leaf[0] - leaf[4]).max() > 0
    # ...but gossip pulled ring neighbors together vs a no-gossip baseline
    progs0 = build_programs(model, mesh, learning_rate=3e-4, gossip_steps=0)
    # gossip_steps=0 -> exact mean; all clients identical afterwards
    mean_cp, _ = progs0.gossip_round(client_params, None, batches, mask, rngs)
    leaf_m = np.asarray(jax.tree.leaves(mean_cp)[0])
    np.testing.assert_allclose(leaf_m[0], leaf_m[7], atol=1e-6)


def test_collapse_equals_mean():
    ds, cache, part, model, mesh, progs, params = _setup()
    cp = progs.broadcast(params)
    w = mesh.shard_clients(jnp.ones((8,)))
    g = progs.collapse(cp, w, params)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_lora_fed_round():
    """Same round program with adapters as the trainable tree."""
    ds, cache, part, model, mesh, progs, params = _setup()
    adapters = lora_lib.init_lora(jax.random.key(3), params, rank=4)
    stacked = progs.broadcast(adapters)
    mask = mesh.shard_clients(jnp.ones((8,)))
    batches, n_ex, rngs = _round_inputs(cache, part, mesh, 0)
    new_ad, stats = progs.gossip_round(stacked, params, batches, mask, rngs)
    assert np.asarray(stats).shape == (8, 3)
    # adapters moved away from zero-init
    b_leaves = [np.abs(np.asarray(v["b"])).max() for v in new_ad.values()
                if "b" in v]
    assert max(b_leaves) > 0


def test_all_masked_round_keeps_params():
    """An all-zero participation mask must not zero the global model."""
    ds, cache, part, model, mesh, progs, params = _setup()
    batches, n_ex, rngs = _round_inputs(cache, part, mesh, 0)
    w0 = mesh.shard_clients(jnp.zeros((8,)))
    out, _ = progs.server_round(params, None, batches, w0, rngs)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_server_rounds_scan_matches_sequential():
    """The on-device multi-round program (R rounds in one jit, the bench /
    static-config fast path) must produce exactly what R sequential
    server_round calls produce."""
    ds, cache, part, model, mesh, progs, params = _setup()
    weights = mesh.shard_clients(jnp.ones((mesh.num_clients,)))

    per_round = []
    for rnd in range(2):
        batches, n_ex, rngs = _round_inputs(cache, part, mesh, rnd)
        per_round.append((batches, rngs))

    # sequential reference
    p_seq = params
    seq_stats = []
    for batches, rngs in per_round:
        p_seq, stats = progs.server_round(p_seq, None, batches, weights, rngs)
        seq_stats.append(np.asarray(stats))

    # stacked [R, C, ...] inputs through the scanned program
    stacked_b = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[b for b, _ in per_round])
    stacked_w = jnp.stack([weights, weights])
    stacked_r = jnp.stack([r for _, r in per_round])
    p_scan, stats = progs.server_rounds(params, None, stacked_b, stacked_w,
                                        stacked_r)
    for a, b in zip(jax.tree.leaves(p_scan), jax.tree.leaves(p_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(np.asarray(stats), np.stack(seq_stats),
                               rtol=2e-5, atol=1e-4)


def test_program_cache_shares_and_evicts():
    """Equal (model, mesh, scalars) build_programs calls return the SAME
    FedPrograms (cross-engine jit reuse); the cache is FIFO-bounded and
    clear_program_cache() empties it."""
    from bcfl_tpu.core.mesh import client_mesh
    from bcfl_tpu.fed import client_step as cs
    from bcfl_tpu.models import build

    cs.clear_program_cache()
    mesh = client_mesh(2)
    m = build("tiny-bert", num_labels=2)
    p1 = cs.build_programs(m, mesh)
    p2 = cs.build_programs(build("tiny-bert", num_labels=2),
                           client_mesh(2))
    assert p1 is p2
    # a differing scalar is a different program set
    p3 = cs.build_programs(m, mesh, learning_rate=1e-3)
    assert p3 is not p1
    # FIFO bound: filling past the cap evicts the oldest entry
    n0 = len(cs._PROGRAM_CACHE)
    for i in range(cs._PROGRAM_CACHE_MAX - n0 + 1):
        cs.build_programs(m, mesh, learning_rate=2e-3 + i * 1e-6)
    assert len(cs._PROGRAM_CACHE) == cs._PROGRAM_CACHE_MAX
    assert cs.build_programs(m, mesh) is not p1  # p1 was evicted (oldest)
    cs.clear_program_cache()
    assert not cs._PROGRAM_CACHE
