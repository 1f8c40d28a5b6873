"""Selective state-space scan (Mamba-2), in its chunked form.

For every head h with a state ``S`` [P, N] that is zero before a row's first
position::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

``x`` [batch, seq, H, P], ``dt`` [batch, seq, H] (positive: after its
softplus), ``A`` [H] (negative), ``B`` and ``C`` [batch, seq, N] (one group:
every head reads the same B and C), ``D`` [H]; ``y`` as ``x``.

The chunked form (state-space duality): a row is cut into chunks of ``chunk``
positions. Inside a chunk the recurrence is a masked product, ``y_i = sum_{j
<= i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j`` with ``cum`` the running sum
of ``dt A`` over the chunk; the state that enters a chunk adds ``exp(cum_i)
S C_i`` and is carried to the next by one small recurrence over the chunks.
The decay between two positions is ``exp`` of a DIFFERENCE of running sums
under the causal mask, never a quotient of two exponentials: ``dt A`` reaches
-16 a position, a chunk's sum -4000, and ``exp(4000)`` is no float32. Decays
and states are float32; the products take their operands in ``x``'s type and
add in float32.

The backward pass (``custom_vjp``) is the chunked form too: what is kept of
the forward pass are the arguments alone; the states that enter the chunks
are carried forward again (the small recurrence, none of the masked
products), then the chunks are taken last to first, each differentiated on
its own with the gradient of the state it hands on. A plain ``jax.grad`` of
the forward scan would keep every chunk's [H, chunk, chunk] decays instead.

Two implementations under the kernel registry's name ``ssm_scan``: this
one in ``jax.numpy`` (the reference, the CPU path and the fallback) and the
Pallas kernels of :mod:`bcfl_tpu.ops.pallas_ssm` (the same chunked form and
rounding points, the state carried in VMEM across a row's chunks), chosen
where one TPU chip is visible for the shapes they take
(``pallas_ssm.supports``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bcfl_tpu.ops import pallas_ssm, registry

DEFAULT_CHUNK = 256


def n_chunks(seq: int, chunk: int = DEFAULT_CHUNK) -> int:
    """The chunks a row of ``seq`` positions is cut into."""
    return -(-seq // chunk)


def _decays(dt, A):
    """``cum`` [.., Q, H]: the running sum of ``dt A`` inside the chunk."""
    return jnp.cumsum(dt.astype(jnp.float32) * A.astype(jnp.float32), axis=-2)


def _handed_on(S, x, dt, A, Bm):
    """The state a chunk hands on: what it received, decayed over the whole
    chunk, and every position's ``dt x B^T`` decayed to the chunk's end."""
    cum = _decays(dt, A)
    to_end = jnp.exp(cum[..., -1:, :] - cum) * dt.astype(jnp.float32)  # [.., Q, H]
    xd = (x.astype(jnp.float32) * to_end[..., None]).astype(x.dtype)
    add = jnp.einsum("...qhp,...qn->...hpn", xd, Bm.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    return jnp.exp(cum[..., -1, :])[..., None, None] * S + add


def _chunk(S, x, dt, A, Bm, Cm, D):
    """One chunk of every row: ``S`` [b, H, P, N] float32 enters, ``x``
    [b, Q, H, P], ``dt`` [b, Q, H], ``Bm``/``Cm`` [b, Q, N]; returns the
    state handed on and ``y`` [b, Q, H, P] in ``x``'s type. The skip ``D x``
    and the cast are the chunk's own: a float32 ``y`` of the whole row,
    finished after the loop, is what XLA then keeps for the backward pass in
    place of the value the caller names (twice its bytes, and ``x`` in
    float32 beside it)."""
    pd = x.dtype
    Q = x.shape[-3]
    cum = _decays(dt, A)  # [b, Q, H]
    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]).astype(pd)
    # inside the chunk: (C_i . B_j) exp(cum_i - cum_j) for j <= i
    cb = jnp.einsum("...in,...jn->...ij", Cm.astype(pd), Bm.astype(pd),
                    preferred_element_type=jnp.float32)  # [b, Q, Q]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    cum_h = jnp.moveaxis(cum, -1, -2)  # [b, H, Q]
    # the mask goes on the exponent: exp(-inf) is 0 with a zero gradient,
    # where a mask on exp's result would multiply an overflow by 0
    decay = jnp.exp(jnp.where(causal, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
    m = (cb[..., None, :, :] * decay).astype(pd)  # [b, H, Q, Q]
    y = jnp.einsum("...hij,...jhp->...ihp", m, xdt, preferred_element_type=jnp.float32)
    # what entered the chunk: exp(cum_i) S C_i
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "...hpn,...in->...ihp", S.astype(pd), Cm.astype(pd),
        preferred_element_type=jnp.float32)
    y = y + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return _handed_on(S, x, dt, A, Bm), y.astype(pd)


def _by_chunk(a, chunk):
    """[b, S, ...] -> [chunks, b, chunk, ...], the tail padded with zeros (a
    padded position has ``dt`` 0: it decays nothing and adds nothing)."""
    b, S = a.shape[:2]
    pad = -S % chunk
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    return jnp.moveaxis(a.reshape((b, (S + pad) // chunk, chunk) + a.shape[2:]), 1, 0)


def _rows(a, S):
    """[chunks, b, chunk, ...] -> [b, S, ...]."""
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape((a.shape[0], -1) + a.shape[3:])[:, :S]


def _state0(x, Bm):
    return jnp.zeros((x.shape[0],) + x.shape[2:] + (Bm.shape[-1],), jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssm_scan_xla(x, dt, A, Bm, Cm, D, chunk=DEFAULT_CHUNK):
    S = x.shape[1]
    xs = tuple(_by_chunk(a, chunk) for a in (x, dt, Bm, Cm))

    def step(state, c):
        xc, dtc, bc, cc = c
        return _chunk(state, xc, dtc, A, bc, cc, D)

    _, y = lax.scan(step, _state0(x, Bm), xs)
    return _rows(y, S)


def _fwd(x, dt, A, Bm, Cm, D, chunk):
    return ssm_scan_xla(x, dt, A, Bm, Cm, D, chunk), (x, dt, A, Bm, Cm, D)


def _bwd(chunk, res, dy):
    x, dt, A, Bm, Cm, D = res
    S = x.shape[1]
    xs = tuple(_by_chunk(a, chunk) for a in (x, dt, Bm, Cm))

    # the states that enter the chunks, carried forward again
    def carry(state, c):
        xc, dtc, bc, _ = c
        return _handed_on(state, xc, dtc, A, bc), state

    _, entered = lax.scan(carry, _state0(x, Bm), xs)

    # last chunk first, each differentiated on its own
    def step(acc, c):
        d_state, dA, dD = acc
        state, (xc, dtc, bc, cc), dyc = c
        _, vjp = jax.vjp(_chunk, state, xc, dtc, A, bc, cc, D)
        d_state, dxc, ddtc, dAc, dbc, dcc, dDc = vjp((d_state, dyc))
        return (d_state, dA + dAc, dD + dDc), (dxc, ddtc, dbc, dcc)

    (_, dA, dD), grads = lax.scan(
        step, (_state0(x, Bm), jnp.zeros(A.shape, A.dtype), jnp.zeros(D.shape, D.dtype)),
        (entered, xs, _by_chunk(dy, chunk)), reverse=True)
    dx, ddt, dB, dC = (_rows(g, S) for g in grads)
    return dx, ddt, dA, dB, dC, dD


ssm_scan_xla.defvjp(_fwd, _bwd)


SSM_SCAN = registry.register_op(registry.KernelOp(
    name="ssm_scan",
    xla=ssm_scan_xla,
    pallas=pallas_ssm.ssm_scan_pallas,
    parity="allclose (the same rounding points, float32 sums in another "
           "order; both pinned against a position-by-position loop in "
           "tests/test_ssm_moe.py)",
    bench_shapes=(
        # the state-space cell's folded step: 2 clients x 1 row of 4096,
        # 128 heads of 64, state 128, bfloat16 operands
        {"label": "ssm-moe-B2-S4096-H128-P64-N128", "B": 2, "S": 4096, "H": 128,
         "P": 64, "N": 128, "chunk": DEFAULT_CHUNK, "dtype": "bfloat16"},
    ),
    supports=pallas_ssm.supports,
))


def ssm_scan(x, dt, A, Bm, Cm, D, chunk: int = DEFAULT_CHUNK, impl: str = "auto"):
    """Dispatch through the kernel registry: the Pallas kernels where
    ``auto`` selects them (one visible TPU chip) for the shapes they take,
    the ``jax.numpy`` form elsewhere. A row whose length is no multiple of
    ``chunk`` is padded inside the op."""
    fn, _ = registry.select("ssm_scan", impl, x, dt, A, Bm, Cm, D, chunk=chunk)
    return fn(x, dt, A, Bm, Cm, D, chunk)
