"""Where the dropout keep-masks of a training step come from.

Dropout draws are an INPUT of the step, like the batch: the reference cannot
derive them from the mathematics, so it derives them from the seed by the
same public recipe that the program uses, written out here call by call
(``jax.random`` and flax's documented per-module key folding), and takes
nothing from the program at run time:

  root     = jax.random.key(seed)                          (FedConfig.seed)
  round    = fold_in(fold_in(root, 4), round_index)
  client   = fold_in(round, client_index)
  step[j]  = jax.random.split(client, local_steps)[j]
  site     = flax's LazyRng(step[j], module path + call count).as_jax_rng()
  keep     = jax.random.bernoulli(site, 1 - rate, shape of the site's input)

A PR that changes how the program draws its masks (another generator, one
draw per step) changes this input and has to come after a benchmark PR that
follows it: PERF.md, Open questions.
"""

from __future__ import annotations

import jax
from flax.core.scope import LazyRng

from . import model as encoder

DROPOUT_LANE = 4  # FedEngine._rngs folds 4 into the root key for this stream


def client_round_key(seed, rnd, client, impl=None):
    root = jax.random.key(seed, impl=impl)
    rk = jax.random.fold_in(jax.random.fold_in(root, DROPOUT_LANE), rnd)
    return jax.random.fold_in(rk, client)


def step_keys(key, steps):
    return jax.random.split(key, steps)


def _site_paths(sizes):
    """``{site: (module path..., call count)}``: the suffix flax folds into
    the step key at each dropout site."""
    out = {"emb": ("encoder", "embeddings", "Dropout_0", 1)}
    for i in range(sizes["num_hidden_layers"]):
        if sizes["share_layers"]:
            # one module applied again and again: its call count runs on
            out[f"attn{i}"] = ("encoder", "layer_shared", "attention", "Dropout_0", i + 1)
            out[f"mlp{i}"] = ("encoder", "layer_shared", "Dropout_0", i + 1)
        else:
            out[f"attn{i}"] = ("encoder", f"layer_{i}", "attention", "Dropout_0", 1)
            out[f"mlp{i}"] = ("encoder", f"layer_{i}", "Dropout_0", 1)
    out["pool"] = ("Dropout_0", 1)
    return out


def keep_masks(step_key, sizes, batch, seq):
    """Boolean keep-masks of every dropout site for one training step."""
    shapes = encoder.dropout_sites(sizes, batch, seq)
    keep = {}
    for site, path in _site_paths(sizes).items():
        k = LazyRng.create(step_key, *path).as_jax_rng()
        keep[site] = jax.random.bernoulli(k, 1.0 - sizes["dropout"], shapes[site])
    return keep
