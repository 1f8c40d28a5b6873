"""The gated delta rule (Gated DeltaNet's linear attention), in its chunked
form.

For every value head h with a state ``S`` [Dk, Dv] that is zero before a
row's first position::

    S <- exp(g_t) S
    S <- S + k_t (beta_t (v_t - S^T k_t))^T        o_t = S^T q_t

``q`` and ``k`` [batch, seq, Hk, Dk], ``v`` [batch, seq, H, Dv], ``g`` and
``beta`` [batch, seq, H] (``g`` <= 0, ``beta`` in (0, 1)); value head h reads
key head ``h // (H / Hk)``; ``o`` as ``v``. The state update reads the state
itself (the ``- S^T k_t`` term), so this is no scan of a fixed transition
like :mod:`bcfl_tpu.ops.ssm_scan`'s.

The chunked form (Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
arXiv:2412.06464; transformers' ``torch_chunk_gated_delta_rule``): a row is
cut into chunks of ``chunk`` positions. Inside a chunk, with ``G`` the
running sum of ``g`` and ``Gam_ij = exp(G_i - G_j)`` for ``j <= i``:

- ``A = (diag(beta) K K^T) * Gam``, strictly below the diagonal, and
  ``T = (I + A)^-1`` by a unit lower-triangular solve;
- ``U = T (beta V)`` and ``W = T (beta exp(G) K)``; the values the chunk
  writes are ``V' = U - W S`` with ``S`` the state that entered;
- ``O = (exp(G) Q) S + ((Q K^T) * Gam)_causal V'``;
- the state handed on is ``exp(G_last) S + (exp(G_last - G) K)^T V'``.

Only ``V'``, the first term of ``O`` and the state handed on read the state,
so the op runs in three parts (the chunk-parallel split of
flash-linear-attention's ``chunk_gated_delta_rule``): everything else, the
``chunk x chunk`` solve among it, once for every chunk of every row in one
go; a loop over the chunks that carries the state and computes ``V'``,
``(exp(G) Q) S`` and the state handed on alone; and the intra-chunk
product, again for every chunk at once, after the loop.

Decays are ``exp`` of a DIFFERENCE of running sums under the causal mask,
never a quotient of two exponentials. Decays, ``A``, ``T`` and the state are
float32; the products take their operands in ``v``'s type (``T`` and the
state rounded to it as operands) and add in float32.

The backward pass (``custom_vjp``) is the chunked form too, as the
state-space scan's: what is kept of the forward pass are the arguments
alone. The state-independent part runs again for every chunk at once (its
``jax.vjp``), the states that enter the chunks are carried forward again
through the loop's part alone, the loop's part is taken last to first, each
chunk differentiated on its own with the gradient of the state it hands
on, and the cotangents of both batched parts are pulled back for every
chunk at once, the solve's among them. Keeping every chunk's state instead
would cost ``chunks x H x Dk x Dv`` float32 a row (1.6 GB for the
benchmark's three mixers at 8192 positions and two clients).

Registered as ``gated_delta_rule`` under the kernel registry, with this
``jax.numpy`` form alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bcfl_tpu.ops import registry

DEFAULT_CHUNK = 64


def n_chunks(seq: int, chunk: int = DEFAULT_CHUNK) -> int:
    """The chunks a row of ``seq`` positions is cut into."""
    return -(-seq // chunk)


def _heads(a, H):
    """A key head's [..., Q, Hk, D] repeated for the value heads that read it."""
    return jnp.repeat(a, H // a.shape[-2], axis=-2)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` [..., Q, Q] float32 strictly below its
    diagonal: one unit lower-triangular solve, batched over every leading
    axis."""
    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    return jax.scipy.linalg.solve_triangular(
        eye + a, jnp.broadcast_to(eye, a.shape), lower=True, unit_diagonal=True)


def _prepare(q, k, v, g, beta):
    """Everything of a chunk that does not read the state, for every chunk
    at once: ``q``/``k`` [..., Q, Hk, Dk], ``v`` [..., Q, H, Dv], ``g``/``beta``
    [..., Q, H] under any leading axes; returns ``(U, W, qg, kd, decay, qk)``
    with heads in front of the positions ([..., H, Q, D] and [..., H, Q, Q]),
    ``decay`` [..., H] the chunk's whole decay and ``qk`` the masked
    ``(Q K^T) * Gam``. ``W`` and ``qk`` are rounded to ``v``'s type, as the
    products take them."""
    pd = v.dtype
    H, Q = v.shape[-2], v.shape[-3]
    qh, kh = (jnp.moveaxis(_heads(a, H), -2, -3) for a in (q, k))  # [..., H, Q, Dk]
    vh = jnp.moveaxis(v, -2, -3)
    G = jnp.moveaxis(jnp.cumsum(g.astype(jnp.float32), axis=-2), -1, -2)  # [..., H, Q]
    bt = jnp.moveaxis(beta.astype(jnp.float32), -1, -2)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # the mask goes on the exponent: exp(-inf) is 0 with a zero gradient
    gam = jnp.exp(jnp.where(causal, G[..., :, None] - G[..., None, :], -jnp.inf))
    kk = jnp.einsum("...id,...jd->...ij", kh.astype(pd), kh.astype(pd),
                    preferred_element_type=jnp.float32)
    strict = jnp.tril(jnp.ones((Q, Q), bool), -1)
    Tp = _unit_lower_inverse(jnp.where(strict, bt[..., :, None] * kk * gam, 0.0)).astype(pd)
    bv = (vh.astype(jnp.float32) * bt[..., None]).astype(pd)
    bk = (kh.astype(jnp.float32) * (bt * jnp.exp(G))[..., None]).astype(pd)
    U = jnp.einsum("...ij,...jd->...id", Tp, bv, preferred_element_type=jnp.float32)
    W = jnp.einsum("...ij,...jd->...id", Tp, bk, preferred_element_type=jnp.float32).astype(pd)
    qg = (qh.astype(jnp.float32) * jnp.exp(G)[..., None]).astype(pd)
    kd = (kh.astype(jnp.float32) * jnp.exp(G[..., -1:] - G)[..., None]).astype(pd)
    # inside the chunk: (q_i . k_j) Gam_ij for j <= i
    qk = (jnp.einsum("...id,...jd->...ij", qh.astype(pd), kh.astype(pd),
                     preferred_element_type=jnp.float32) * gam).astype(pd)
    return U, W, qg, kd, jnp.exp(G[..., -1]), qk


def _step(S, U, W, qg, kd, decay):
    """What of one chunk reads the state ``S`` [b, H, Dk, Dv] (float32) that
    enters it: the state handed on, the values the chunk writes ``V' = U - W
    S`` in ``v``'s type, and the part of the output the state gives, ``qg S``
    (float32). The state is rounded to ``v``'s type once for each product and
    ``V'`` once for each use, as operands."""
    pd = W.dtype
    vn = U - jnp.einsum("...ik,...kv->...iv", W, S.astype(pd), preferred_element_type=jnp.float32)
    inter = jnp.einsum("...ik,...kv->...iv", qg, S.astype(pd), preferred_element_type=jnp.float32)
    S = decay[..., None, None] * S + jnp.einsum(
        "...ik,...iv->...kv", kd, vn.astype(pd), preferred_element_type=jnp.float32)
    return S, vn.astype(pd), inter


def _intra(qk, vn):
    """The output a chunk's own positions give: ``((Q K^T) * Gam)_causal V'``,
    float32."""
    return jnp.einsum("...ij,...jv->...iv", qk, vn, preferred_element_type=jnp.float32)


def _by_chunk(a, chunk):
    """[b, S, ...] -> [chunks, b, chunk, ...], the tail padded with zeros (a
    padded position has ``g`` 0 and ``beta`` 0: it decays nothing and writes
    nothing, and it lies after every real position of its row)."""
    b, S = a.shape[:2]
    pad = -S % chunk
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    return jnp.moveaxis(a.reshape((b, (S + pad) // chunk, chunk) + a.shape[2:]), 1, 0)


def _rows(a, S):
    """[chunks, b, chunk, ...] -> [b, S, ...]."""
    a = jnp.moveaxis(a, 0, 1)
    return a.reshape((a.shape[0], -1) + a.shape[3:])[:, :S]


def _state0(k, v):
    return jnp.zeros((v.shape[0], v.shape[2], k.shape[-1], v.shape[-1]), jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gated_delta_rule_xla(q, k, v, g, beta, chunk=DEFAULT_CHUNK):
    S = v.shape[1]
    *loop, qk = _prepare(*(_by_chunk(a, chunk) for a in (q, k, v, g, beta)))

    def step(state, c):
        state, vn, inter = _step(state, *c)
        return state, (vn, inter)

    _, (vn, inter) = lax.scan(step, _state0(k, v), loop)
    o = inter + _intra(qk, vn)
    return _rows(jnp.moveaxis(o, -3, -2).astype(v.dtype), S)


def _fwd(q, k, v, g, beta, chunk):
    return gated_delta_rule_xla(q, k, v, g, beta, chunk), (q, k, v, g, beta)


def _bwd(chunk, res, do):
    q, k, v, g, beta = res
    S = v.shape[1]
    (*loop, qk), prepared = jax.vjp(_prepare, *(_by_chunk(a, chunk) for a in res))

    # the states that enter the chunks and the values each writes, carried
    # forward again
    def carry(state, c):
        nxt, vn, _ = _step(state, *c)
        return nxt, (state, vn)

    _, (entered, vn) = lax.scan(carry, _state0(k, v), loop)

    # the output's cotangent, [chunks, b, H, Q, Dv]: the intra-chunk
    # product's part for every chunk at once
    do = jnp.moveaxis(_by_chunk(do, chunk), -2, -3)
    d_qk, d_vn = jax.vjp(_intra, qk, vn)[1](do.astype(jnp.float32))

    # last chunk first, each chunk's state-dependent part differentiated on
    # its own with the gradient of the state it hands on
    def step(d_state, c):
        state, xc, d_vn, doc = c
        _, vjp = jax.vjp(_step, state, *xc)
        d_state, *grads = vjp((d_state, d_vn, doc.astype(jnp.float32)))
        return d_state, tuple(grads)

    _, grads = lax.scan(step, _state0(k, v), (entered, loop, d_vn, do), reverse=True)
    return tuple(_rows(gr, S).astype(a.dtype) for gr, a in zip(prepared((*grads, d_qk)), res))


gated_delta_rule_xla.defvjp(_fwd, _bwd)


GATED_DELTA_RULE = registry.register_op(registry.KernelOp(
    name="gated_delta_rule",
    xla=gated_delta_rule_xla,
    parity="the jax.numpy form alone (pinned against a position-by-position "
           "loop in tests/test_gated_delta.py)",
    bench_shapes=(
        # the Gated DeltaNet cell's folded step: 2 clients x 1 row of 8192,
        # 32 value heads over 16 key heads of 128, bfloat16 operands
        {"label": "gdn-B2-S8192-Hk16-H32-D128", "B": 2, "S": 8192, "Hk": 16, "H": 32,
         "D": 128, "chunk": DEFAULT_CHUNK, "dtype": "bfloat16"},
    ),
))


def gated_delta_rule(q, k, v, g, beta, chunk: int = DEFAULT_CHUNK, impl: str = "auto"):
    """Dispatch through the kernel registry (the ``jax.numpy`` form under
    every request). A row whose length is no multiple of ``chunk`` is padded
    inside the op."""
    fn, _ = registry.select("gated_delta_rule", impl, q, k, v, g, beta, chunk=chunk)
    return fn(q, k, v, g, beta, chunk)
