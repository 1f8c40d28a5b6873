import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcfl_tpu.core import client_mesh
from bcfl_tpu.parallel.gspmd import gossip_mix, masked_weighted_mean, mix_with_matrix, ring_shift


def _run_sharded(mesh, fn, *args, replicated_out=False):
    """``fn`` under jit on client-sharded inputs: the SPMD partitioner
    inserts the collectives over the (8, 5 or 8-device) clients axis."""
    args = [mesh.shard_clients(jax.tree.map(jnp.asarray, a)) for a in args]
    out = mesh.replicated() if replicated_out else mesh.client_sharding()
    return jax.jit(fn, out_shardings=out)(*args)


@pytest.mark.parametrize("num_clients", [8, 10, 16])
def test_masked_weighted_mean_matches_numpy(num_clients):
    mesh = client_mesh(num_clients)
    x = np.random.default_rng(0).normal(size=(num_clients, 3, 4)).astype(np.float32)
    w = np.arange(1, num_clients + 1, dtype=np.float32)
    w[2] = 0.0  # anomaly-masked client
    tree = {"p": x}

    out = _run_sharded(
        mesh, masked_weighted_mean, tree, w, replicated_out=True)
    want = (x * w[:, None, None]).sum(0) / w.sum()
    np.testing.assert_allclose(np.asarray(out["p"]), want, rtol=1e-5)


@pytest.mark.parametrize("num_clients", [8, 10])
@pytest.mark.parametrize("direction", [+1, -1])
def test_ring_shift_global_order(num_clients, direction):
    mesh = client_mesh(num_clients)
    x = np.arange(num_clients, dtype=np.float32).reshape(num_clients, 1)
    out = _run_sharded(
        mesh, lambda t: ring_shift(t, direction), {"x": x}
    )
    got = np.asarray(out["x"]).ravel()
    want = np.roll(np.arange(num_clients), -direction)
    np.testing.assert_array_equal(got, want)


def test_gossip_mix_converges_to_mean():
    num_clients = 8
    mesh = client_mesh(num_clients)
    x = np.random.default_rng(1).normal(size=(num_clients, 4)).astype(np.float32)
    mask = np.ones((num_clients,), np.float32)
    out = _run_sharded(
        mesh,
        lambda t, m: gossip_mix(t, m, alpha=0.6, steps=60),
        {"x": x}, mask,
    )
    got = np.asarray(out["x"])
    want = np.broadcast_to(x.mean(0), got.shape)
    np.testing.assert_allclose(got, want, atol=1e-3)
    # mean is preserved at every step (doubly-stochastic mixing)
    np.testing.assert_allclose(got.mean(0), x.mean(0), atol=1e-5)


def test_gossip_mix_isolates_masked_client():
    num_clients = 8
    mesh = client_mesh(num_clients)
    x = np.zeros((num_clients, 2), np.float32)
    x[3] = 100.0  # poisoned client
    mask = np.ones((num_clients,), np.float32)
    mask[3] = 0.0
    out = _run_sharded(
        mesh,
        lambda t, m: gossip_mix(t, m, alpha=0.5, steps=20),
        {"x": x}, mask,
    )
    got = np.asarray(out["x"])
    np.testing.assert_allclose(got[3], 100.0)  # frozen, not drifted
    honest = np.delete(got, 3, axis=0)
    assert np.abs(honest).max() < 1e-4  # poison never leaked


def test_mix_with_matrix_matches_dense_einsum():
    num_clients = 8
    mesh = client_mesh(num_clients)
    rng = np.random.default_rng(2)
    x = rng.normal(size=(num_clients, 5)).astype(np.float32)
    W = rng.random((num_clients, num_clients)).astype(np.float32)
    W = W / W.sum(1, keepdims=True)
    out = _run_sharded(
        mesh,
        lambda t: mix_with_matrix(t, jnp.asarray(W)),
        {"x": x},
    )
    np.testing.assert_allclose(np.asarray(out["x"]), W @ x, rtol=1e-4, atol=1e-6)
