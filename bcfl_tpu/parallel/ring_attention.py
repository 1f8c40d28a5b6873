"""Ring attention: exact attention over sequences sharded across chips.

Long-context is first-class here even though the reference truncates at 512
tokens (SURVEY.md §5 "long-context: absent"). The sequence axis is sharded
over a mesh axis; each chip holds a ``[B, H, S/n, D]`` Q/K/V shard and the
KV shards rotate around the ring via ``lax.ppermute`` (ICI neighbor
exchanges, no all-to-all). Each hop combines the local block's contribution
with the FlashAttention online-softmax recurrence, so the result is EXACT
full attention with O(S/n) memory per chip and compute/communication overlap
left to XLA's latency-hiding scheduler.

Use inside ``shard_map`` with the sequence dim sharded over ``axis_name``;
:func:`ring_attention_sharded` wraps that for convenience.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


NEG = -1e30  # large-negative, not -inf: no NaN path on fully-masked blocks


def _block(q, k, v, key_bias, scale, dead):
    """One KV block's contribution: block max, normalizer, unnormalized out.

    ``dead`` [B, 1|H, Sq, Sk] marks masked (query, key) pairs; fully-dead
    blocks self-correct in the outer recurrence (their mass is scaled by
    ``exp(NEG - m_real)`` = 0 once any live block arrives).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    s = s + key_bias[:, None, None, :].astype(jnp.float32)
    s = jnp.where(dead, NEG, s)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(dead, 0.0, jnp.exp(s - m))
    l = p.sum(axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m, l, o


def _online_merge(acc, m, l, bm, bl, bo):
    """One step of the FlashAttention online-softmax recurrence, shared by
    the shard_map and GSPMD implementations (rank-agnostic: broadcasts over
    whatever leading dims the block stats carry)."""
    m_new = jnp.maximum(m, bm)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(bm - m_new)
    return acc * alpha + bo * beta, m_new, l * alpha + bl * beta


def ring_attention(
    q: jnp.ndarray,  # [B, H, Sq_local, D]  (inside shard_map)
    k: jnp.ndarray,  # [B, H, Sk_local, D]
    v: jnp.ndarray,
    key_bias: Optional[jnp.ndarray],  # [B, Sk_local] additive key mask
    axis_name: str,
    causal: bool = False,
) -> jnp.ndarray:
    """Exact attention with KV rotating around the ``axis_name`` ring.

    With ``causal=True`` the global causal triangle is reconstructed from
    ring position: at hop ``t`` a chip at ring index ``r`` holds the KV shard
    originally at ``(r - t) mod n``, so global key positions are
    ``shard_id * Sk + local_idx`` — no dense [S, S] mask ever exists.
    """
    n = lax.psum(1, axis_name)
    r = lax.axis_index(axis_name)
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale = 1.0 / (D ** 0.5)
    qf = q.astype(jnp.float32)
    if key_bias is None:
        key_bias = jnp.zeros((B, Sk), jnp.float32)

    qpos = r * Sq + jnp.arange(Sq)[:, None]  # global query positions [Sq, 1]
    kloc = jnp.arange(Sk)[None, :]  # local key offsets [1, Sk]

    # ppermute: each chip sends its KV shard to the next ring position
    perm = [(i, (i + 1) % n) for i in range(n)]

    def _merge(t, carry):
        acc, m, l, kc, vc, bc = carry
        if causal:
            shard = (r - t) % n  # which global shard this chip now holds
            kpos = shard * Sk + kloc
            dead = (kpos > qpos)[None, None]  # [1, 1, Sq, Sk]
        else:
            dead = jnp.zeros((1, 1, 1, 1), bool)
        bm, bl, bo = _block(qf, kc, vc, bc, scale, dead)
        acc, m, l = _online_merge(acc, m, l, bm, bl, bo)
        return acc, m, l, kc, vc, bc

    def step(t, carry):
        acc, m, l, kc, vc, bc = _merge(t, carry)
        kc, vc, bc = lax.ppermute((kc, vc, bc), axis_name, perm)
        return acc, m, l, kc, vc, bc

    acc = jnp.zeros((B, H, Sq, D), jnp.float32)
    m0 = jnp.full((B, H, Sq, 1), NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Sq, 1), jnp.float32)
    # n-1 [merge; rotate] hops, then merge the final shard without the
    # (otherwise discarded) n-th rotate — one full-KV ICI exchange saved
    carry = lax.fori_loop(0, n - 1, step, (acc, m0, l0, k, v, key_bias))
    acc, m, l, *_ = _merge(n - 1, carry)
    return (acc / jnp.maximum(l, 1e-9)).astype(q.dtype)


def ring_attention_sharded(
    q: jnp.ndarray,  # [B, H, S, D] global
    k: jnp.ndarray,
    v: jnp.ndarray,
    key_bias: Optional[jnp.ndarray],  # [B, S]
    mesh: Mesh,
    axis_name: str = "seq",
    causal: bool = False,
) -> jnp.ndarray:
    """Convenience wrapper: shard the sequence dim over ``axis_name``, run
    :func:`ring_attention` under ``shard_map``, return the global result."""
    from jax import shard_map

    qs = P(None, None, axis_name, None)
    bs = P(None, axis_name)

    def inner(q, k, v, b):
        return ring_attention(q, k, v, b, axis_name, causal=causal)

    if key_bias is None:
        key_bias = jnp.zeros((q.shape[0], k.shape[2]), jnp.float32)
    fn = shard_map(
        inner, mesh=mesh,
        in_specs=(qs, qs, qs, bs), out_specs=qs, check_vma=False,
    )
    sh = NamedSharding(mesh, qs)
    bsh = NamedSharding(mesh, bs)
    return fn(jax.device_put(q, sh), jax.device_put(k, sh),
              jax.device_put(v, sh), jax.device_put(key_bias, bsh))


def ring_attention_gspmd(
    q: jnp.ndarray,  # [B, H, S, D] global
    k: jnp.ndarray,
    v: jnp.ndarray,
    key_bias: Optional[jnp.ndarray],  # [B, S]
    mesh: Mesh,
    axis_name: str = "seq",
    causal: bool = False,
) -> jnp.ndarray:
    """GSPMD twin of :func:`ring_attention_sharded` — same ring math, no
    shard_map.

    The global arrays are viewed as ``[B, H, n, S/n, D]`` with the block dim
    sharded over ``axis_name``. Each hop computes the block-diagonal
    q-block x k-block product (local on every shard — the einsum never
    contracts across the sharded dim) and rolls the KV blocks one position
    along it; XLA lowers the roll on a sharded dim to collective-permute,
    exactly the manual implementation's ppermute ring. Shipped because
    shard_map executed ~200x slower than jit-with-annotations in the one
    bisection on record (PERF.md "Earlier recordings"), which made the
    manual SP path unusable precisely where it matters; parity is pinned by
    ``tests/test_ring_attention.py``.
    """
    B, H, S, D = q.shape
    n = mesh.shape[axis_name]
    if S % n:
        raise ValueError(f"seq {S} not divisible by {axis_name} size {n}")
    blk = S // n
    scale = 1.0 / (D ** 0.5)
    if key_bias is None:
        key_bias = jnp.zeros((B, S), jnp.float32)

    bsh = NamedSharding(mesh, P(None, None, axis_name, None, None))
    kbsh = NamedSharding(mesh, P(None, axis_name, None))
    _c = lax.with_sharding_constraint
    qb = _c(q.astype(jnp.float32).reshape(B, H, n, blk, D), bsh)
    k0 = _c(k.astype(jnp.float32).reshape(B, H, n, blk, D), bsh)
    v0 = _c(v.astype(jnp.float32).reshape(B, H, n, blk, D), bsh)
    b0 = _c(key_bias.astype(jnp.float32).reshape(B, n, blk), kbsh)

    q_blk = jnp.arange(n)  # global block id at each block-dim position
    qpos = q_blk[:, None] * blk + jnp.arange(blk)[None, :]  # [n, blk_q]

    def _merge(t, carry):
        acc, m, l, kc, vc, bc = carry
        if causal:
            # after t rolls, block-dim position r holds global block r - t
            kpos = ((q_blk - t) % n)[:, None] * blk + jnp.arange(blk)[None, :]
            dead = (kpos[:, None, :] > qpos[:, :, None])[None, None]
        else:
            dead = jnp.zeros((1, 1, n, 1, 1), bool)
        # the block-dim einsum never contracts across blocks, so the scoring
        # math is exactly _block vmapped over the (sharded) block dim — ONE
        # copy of the numerically delicate flash-block computation
        bm, bl, bo = jax.vmap(_block, in_axes=(2, 2, 2, 1, None, 2),
                              out_axes=(2, 2, 2))(qb, kc, vc, bc, scale, dead)
        acc, m, l = _online_merge(acc, m, l, bm, bl, bo)
        return acc, m, l, kc, vc, bc

    def hop(t, carry):
        acc, m, l, kc, vc, bc = _merge(t, carry)
        # roll on the sharded block dim -> collective-permute (each shard
        # holds exactly one block)
        kc = _c(jnp.roll(kc, 1, axis=2), bsh)
        vc = _c(jnp.roll(vc, 1, axis=2), bsh)
        bc = _c(jnp.roll(bc, 1, axis=1), kbsh)
        return acc, m, l, kc, vc, bc

    acc = _c(jnp.zeros((B, H, n, blk, D), jnp.float32), bsh)
    m0 = jnp.full((B, H, n, blk, 1), NEG, jnp.float32)
    l0 = jnp.zeros((B, H, n, blk, 1), jnp.float32)
    # n-1 [merge; rotate] hops, then merge the final position without the
    # otherwise-discarded n-th rotate (same saving as the manual impl)
    carry = lax.fori_loop(0, n - 1, hop, (acc, m0, l0, k0, v0, b0))
    acc, m, l, *_ = _merge(n - 1, carry)
    out = (acc / jnp.maximum(l, 1e-9)).reshape(B, H, S, D)
    return _c(out, NamedSharding(mesh, P(None, None, axis_name, None))
              ).astype(q.dtype)
