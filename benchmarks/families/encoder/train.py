"""Plain reference of a federated round: each client's local AdamW steps from
the round's global parameters with a fresh optimizer state, then the
example-weighted mean of the clients' parameters under the round's mask.
Float32 throughout; one client and one step at a time, so that it fits beside
nothing else on the chip. Imports nothing of the program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import dropout
from . import model as encoder


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision", "half_batch"))
def _local_step(params, mu, nu, t, batch, step_key, hp, sizes_key, precision,
                half_batch=False):
    sizes = dict(sizes_key)
    B, S = batch["ids"].shape
    keep = dropout.keep_masks(step_key, sizes, B, S)
    if half_batch:
        # the fault "half of the batch left out, the mean taken over the rest"
        half = jnp.arange(B) < (B // 2)
        batch = dict(batch, example_mask=batch["example_mask"] * half)
    (loss, (correct, n)), g = jax.value_and_grad(encoder.loss_fn, has_aux=True)(
        params, sizes, batch, keep, precision)
    t = t + 1
    b1, b2 = hp["b1"], hp["b2"]
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    c1 = 1 - b1 ** t
    c2 = 1 - b2 ** t

    def upd(p, m, v):
        return p - hp["lr"] * ((m / c1) / (jnp.sqrt(v / c2) + hp["eps"]) + hp["wd"] * p)

    params = jax.tree.map(upd, params, mu, nu)
    gnorm = jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x)), g)
    return params, mu, nu, t, loss * n, correct, n, gnorm


def _sizes_key(sizes):
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "intermediate_size", "embedding_size", "share_layers",
            "layer_norm_eps", "dropout")
    return tuple((k, sizes[k]) for k in keys)


def local_train(global_params, sizes, training, batches, key, precision="f32",
                half_batch=False):
    """One client's round: ``steps`` AdamW steps from ``global_params`` with a
    fresh state. ``batches`` leaves are [steps, B, ...]. Returns the new
    parameters, [sum loss*n, correct, n] and the first step's per-leaf
    gradient norms."""
    hp = {"lr": jnp.float32(training["learning_rate"]), "b1": jnp.float32(training["b1"]),
          "b2": jnp.float32(training["b2"]), "eps": jnp.float32(training["eps"]),
          "wd": jnp.float32(training["weight_decay"])}
    steps = int(batches["ids"].shape[0])
    keys = dropout.step_keys(key, steps)
    p = global_params
    mu = jax.tree.map(jnp.zeros_like, p)
    nu = jax.tree.map(jnp.zeros_like, p)
    t = jnp.float32(0)
    stats = np.zeros(3)
    gnorm0 = None
    for j in range(steps):
        b = {k: v[j] for k, v in batches.items()}
        p, mu, nu, t, ln, correct, n, gnorm = _local_step(
            p, mu, nu, t, b, keys[j], hp, _sizes_key(sizes), precision, half_batch)
        stats += np.array([float(ln), float(correct), float(n)])
        if j == 0:
            gnorm0 = gnorm
    return p, stats, gnorm0


def aggregate(client_params, weights, fallback):
    """Weighted mean over clients; an all-zero weight keeps ``fallback``."""
    w = np.asarray(weights, np.float64)
    den = float(w.sum())
    if den <= 0.0:
        return fallback
    out = jax.tree.map(lambda x: jnp.zeros_like(x), fallback)
    for wc, pc in zip(w, client_params):
        if wc != 0.0:
            out = jax.tree.map(lambda o, x: o + jnp.float32(wc / den) * x, out, pc)
    return out


def run_rounds(params0, sizes, training, traffic, seed, masks, n_ex,
               precision="f32", half_batch=False, drop_client=None, prng_impl=None):
    """Follow the first ``len(masks)`` rounds. ``traffic`` leaves are
    [C, steps, B, ...] (the same batches every round, as the cell states);
    ``masks[r]`` is the participation mask round ``r`` really had, ``n_ex``
    the clients' example counts. Returns per-round losses, the final global
    parameters and the first round's per-leaf gradient norms (the largest
    over the clients).

    ``half_batch`` and ``drop_client`` plant faults that the cell can have,
    for the readings in PERF.md (a state left unchanged needs no run)."""
    C = int(traffic["ids"].shape[0])
    g = params0
    losses, gnorm0 = [], None
    for r, mask in enumerate(masks):
        new, tot = [], np.zeros(3)
        for c in range(C):
            key = dropout.client_round_key(seed, r, c, impl=prng_impl)
            b = {k: v[c] for k, v in traffic.items()}
            p, stats, gn = local_train(g, sizes, training, b, key, precision, half_batch)
            new.append(p)
            tot += stats
            if r == 0:
                gnorm0 = gn if gnorm0 is None else jax.tree.map(jnp.maximum, gnorm0, gn)
        losses.append(tot[0] / max(tot[2], 1.0))
        w = np.asarray(mask, np.float64) * np.asarray(n_ex, np.float64)
        if drop_client is not None:
            w[drop_client] = 0.0
        g = aggregate(new, w, g)
        del new
    return losses, g, gnorm0
