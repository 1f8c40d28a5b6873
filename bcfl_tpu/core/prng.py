"""Deterministic PRNG threading.

The reference seeds dataset shuffles (``seed=42``,
``src/Servercase/server_IID_IMDB.py:68``) but draws client subsets with an
unseeded ``random.sample`` (``:79-80``), so runs are not reproducible. Here one
root key is folded per (round, client) so every sampling decision is
deterministic and independent across clients and rounds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def fold_round(key: jax.Array, round_idx: int) -> jax.Array:
    return jax.random.fold_in(key, round_idx)


def client_round_keys(key: jax.Array, clients, round_idx: int) -> jax.Array:
    """[num_clients, 2] stacked keys, one per client, distinct per round.

    ``clients`` is a count (keys for ids ``0..n-1``) or an explicit id
    vector — cohort mode (SCALING.md) passes the round's sampled REGISTRY
    ids, so a client's stream depends only on ``(seed, id, round)``, never
    on which cohort slot it landed in."""
    rk = fold_round(key, round_idx)
    ids = (jnp.arange(clients) if isinstance(clients, (int, np.integer))
           else jnp.asarray(np.asarray(clients), jnp.int32))
    return jax.vmap(lambda c: jax.random.fold_in(rk, c))(ids)


@jax.jit
def client_key_data(key: jax.Array, ids: jax.Array,
                    rounds: jax.Array) -> jax.Array:
    """The key data of :func:`client_round_keys` for a whole dispatch, as
    ONE program: ``(key, ids[k, C] int32, rounds[k] int32) -> [k, C, K]
    uint32``, row ``i`` being ``key_data(client_round_keys(key, ids[i],
    rounds[i]))`` bit for bit; a scalar ``rounds`` with ``ids[C]`` gives the
    one round's ``[C, K]``. Rounds and ids are operands, so one executable
    per ``(k, C, key implementation)`` serves every dispatch of a run,
    where the eager definition launches a handful of small device programs
    a round with the chip idle (PERF.md section 6, PR 26).

    The rounds go through ``lax.map``, not a second ``vmap``:
    ``unsafe_rbg``'s ``fold_in`` batches differently under a nested vmap
    and would give other keys."""
    def one_round(rnd, row):
        rk = jax.random.fold_in(key, rnd)
        return jax.random.key_data(
            jax.vmap(lambda c: jax.random.fold_in(rk, c))(row))

    if rounds.ndim == 0:
        return one_round(rounds, ids)
    return lax.map(lambda x: one_round(*x), (rounds, ids))
