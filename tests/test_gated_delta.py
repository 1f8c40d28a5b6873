"""The gated delta rule in its chunked form (``ops/gated_delta.py``) against
the definition, a position at a time, in float32 on the CPU.

Tolerance 2e-4 of the largest value, for values and every gradient. Both
sides are float32; the chunked form adds a chunk's positions through a
triangular solve and masked products where the loop makes up to 150
sequential rank-one updates, and XLA:CPU's products add in an order of their
own: the cases here read under 2e-6, and 2e-4 leaves a factor of a hundred
for the fast heads, whose decays inside a chunk span e^-1000."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from bcfl_tpu.ops import registry
from bcfl_tpu.ops.gated_delta import DEFAULT_CHUNK, gated_delta_rule, n_chunks


def _loop(q, k, v, g, beta):
    """The definition: ``S <- exp(g) S``, ``S <- S + k (beta (v - S^T k))^T``,
    ``o = S^T q``, a position at a time; value head j reads key head
    ``j // (H / Hk)``."""
    H = v.shape[2]
    q, k = (jnp.repeat(a, H // a.shape[2], axis=2) for a in (q, k))

    def row(q, k, v, g, beta):
        def step(S, t):
            qt, kt, vt, gt, bt = t
            S = jnp.exp(gt)[:, None, None] * S
            S = S + kt[:, :, None] * (bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt)))[:, None, :]
            return S, jnp.einsum("hkv,hk->hv", S, qt)

        S0 = jnp.zeros((H, k.shape[-1], v.shape[-1]))
        return lax.scan(step, S0, (q, k, v, g, beta))[1]

    return jax.vmap(row)(q, k, v, g, beta)


def _inputs(S, decay, Hk=2, H=4, D=16, seed=0):
    """l2-normalised keys and queries (queries scaled by D^-1/2), ``beta`` in
    (0, 1) and ``g`` about ``decay`` a position (0.5x to 1.5x over the heads)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (2, S, Hk, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (2, S, Hk, D)))
    v = jax.random.normal(ks[2], (2, S, H, D))
    g = decay * jnp.linspace(0.5, 1.5, H) * jnp.exp(0.3 * jax.random.normal(ks[3], (2, S, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, S, H)))
    return q, k, v, g, beta


#: (S, chunk, key heads, value heads): a tail chunk at the published chunk of
#: 64 (150 positions: two whole chunks and 22), chunks that divide the row,
#: and one partial chunk
CASES = [(150, 64, 2, 4), (96, 32, 2, 4), (40, 64, 1, 2)]


@pytest.mark.parametrize("S,chunk,Hk,H", CASES, ids=["tail-chunk", "chunks-divide", "one-partial-chunk"])
@pytest.mark.parametrize("decay", [-0.002, -16.0], ids=["slow", "fast"])
def test_the_chunked_rule_against_a_position_by_position_loop(S, chunk, Hk, H, decay):
    """Values and the gradients of q, k, v, g and beta. Slow heads: the state
    crosses every chunk boundary nearly whole; fast ones: a chunk's running
    sum of ``g`` reaches -1500, and only a difference of running sums under
    the mask keeps the decays finite."""
    args = _inputs(S, decay, Hk, H)
    t = jax.random.normal(jax.random.key(7), args[2].shape)
    with jax.default_matmul_precision("highest"):
        got = gated_delta_rule(*args, chunk=chunk)
        want = _loop(*args)
        g_got = jax.grad(lambda *a: (gated_delta_rule(*a, chunk=chunk) * t).sum(), range(5))(*args)
        g_want = jax.grad(lambda *a: (_loop(*a) * t).sum(), range(5))(*args)
    assert got.shape == want.shape and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-4 * float(jnp.abs(want).max()))
    for name, a, b in zip(("q", "k", "v", "g", "beta"), g_got, g_want):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()), err_msg=name)


def test_one_state_is_carried_over_several_chunks():
    """With slow heads a rule that started every chunk from zero would be far
    off: the first chunk agrees, every later one does not."""
    args = _inputs(256, -0.002)
    whole = gated_delta_rule(*args, chunk=64)
    cut = jnp.concatenate([gated_delta_rule(*(a[:, i:i + 64] for a in args), chunk=64)
                           for i in range(0, 256, 64)], 1)
    np.testing.assert_allclose(cut[:, :64], whole[:, :64], atol=1e-5)
    for i in range(64, 256, 64):
        assert float(jnp.abs(cut[:, i:i + 64] - whole[:, i:i + 64]).max()) > 0.1


def test_a_padded_tail_changes_no_real_position():
    """A row's padding lies at its tail: whatever valid input the padded
    positions hold, the real positions' outputs and the gradients they send back are those
    of the row cut at its last real position, chunk boundary or not."""
    L = 101
    other = _inputs(150, -3.0, seed=5)  # what a padded position may hold: any valid input
    junk = tuple(a.at[:, L:].set(b[:, L:]) for a, b in zip(_inputs(150, -0.05), other))
    v = junk[2]
    t = jax.random.normal(jax.random.key(3), v.shape).at[:, L:].set(0.0)
    with jax.default_matmul_precision("highest"):
        padded = gated_delta_rule(*junk)
        alone = gated_delta_rule(*(a[:, :L] for a in junk))
        gp = jax.grad(lambda *a: (gated_delta_rule(*a) * t).sum(), range(5))(*junk)
        ga = jax.grad(lambda *a: (gated_delta_rule(*a) * t[:, :L]).sum(), range(5))(
            *(a[:, :L] for a in junk))
    np.testing.assert_allclose(padded[:, :L], alone, atol=1e-5)
    for a, b in zip(gp, ga):
        np.testing.assert_allclose(a[:, :L], b, atol=1e-5)
        assert float(jnp.abs(a[:, L:]).max()) == 0.0


def test_bfloat16_operands_float32_state():
    """In the compute type the products round their operands and the decays,
    the triangular solve and the carried state stay float32: close to the
    float32 rule by bfloat16's rounding, not by float32's."""
    args = _inputs(192, -0.02)
    want = gated_delta_rule(*args)
    q, k, v, g, beta = args
    got = gated_delta_rule(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                           g, beta)
    assert got.dtype == jnp.bfloat16
    err = float(jnp.abs(got.astype(jnp.float32) - want).max() / jnp.abs(want).max())
    assert 1e-5 < err < 3e-2, err


def test_the_rule_is_in_the_kernel_registry():
    """One implementation, ``jax.numpy``, under every request; chunks of 64
    by default, a row of 8192 in 128 of them."""
    op = registry.get_op("gated_delta_rule")
    assert not op.has_pallas and registry.resolve("gated_delta_rule", "pallas")[1] == "xla"
    assert DEFAULT_CHUNK == 64 and n_chunks(8192) == 128 and n_chunks(150) == 3
