"""core.fence: the one device-completion seam (``jax.block_until_ready``).

These tests pin the API contract — arbitrary trees: params, PRNG keys,
empty, host-only leaves, sharded — that the engine/bench call sites rely on.
"""

import jax
import jax.numpy as jnp
import pytest

from bcfl_tpu.core.fence import fence
from bcfl_tpu.core.mesh import client_mesh


def test_fence_param_tree():
    tree = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,), jnp.bfloat16)}
    assert fence(tree) is None


def test_fence_scalar_and_empty():
    fence(jnp.float32(3.0))
    fence({})
    fence(None)
    fence({"n": 3, "s": "host"})  # host-only leaves


def test_fence_key_tree():
    keys = jax.random.split(jax.random.key(0), 4)
    fence({"k": keys})


def test_fence_int_and_bool():
    fence(jnp.arange(3))
    fence(jnp.arange(3) > 1)


def test_fence_zero_size_leaf():
    fence(jnp.zeros((0, 4)))
    fence({"a": jnp.zeros((0,)), "b": jax.jit(lambda: jnp.ones((8, 8)))()})


def test_fence_complex_dtype():
    fence(jnp.ones((4,), jnp.complex64))


def test_fence_mixed_host_and_device_leaves():
    import numpy as np

    fence({"step": np.asarray(3), "params": jax.jit(lambda: jnp.ones(4))()})


def test_fence_sharded_output():
    mesh = client_mesh(8)
    x = jax.device_put(jnp.arange(8.0), mesh.client_sharding())
    y = jax.jit(lambda a: a * 2)(x)
    fence(y)


def test_fence_after_jit_matches_value():
    y = jax.jit(lambda a: a + 1)(jnp.arange(4))
    fence(y)
    assert int(y[0]) == 1
